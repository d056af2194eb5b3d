"""The control of ``correct``: the reference in the engine's place, one
precision lower than the configuration states, through the same front,
traffic and check. Every run should come out ``correct: false``; its
readings are the upper end from which ``bench/limits/`` are set.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \
        --seconds 51

The configurations state float32 at ``Precision.HIGHEST``; the control
computes the distances at ``high`` (three bfloat16 passes), over the
cell's own traffic and window. The benchmark's own runs never run it.
Prints one JSON line per seed.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    import argparse

    sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                    if p not in sys.path]
    from bench import harness, reference, spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load(ROOT, args.workload)
    try:
        harness.require_accelerator(cell.chips)
    except harness.NoAccelerator as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache(cell.config)

    def factory(cell, rows, data_dev, spill_dir):
        return reference.ReferenceEngine(data_dev, "high")

    for seed in (int(s) for s in args.seeds.split(",")):
        line = harness.run(ROOT, args.workload, seed, args.seconds, False,
                           time.perf_counter(), engine_factory=factory)
        print(json.dumps({"control": "high", "seed": seed,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
