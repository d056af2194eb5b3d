"""Find the knee of an open-loop cell: the highest offered rate that is
answered at >= 97% of the offer with no growing queue (the median
latency of the last quarter of requests at most GROWTH times that of the
first quarter).

    python3 bench/sweep.py --workload disk-ng-open --seed 5 \
        --rates 30,45,60,75,90 --seconds 15

One process and one set-up; then, for each rate in turn, a fresh front
and an open-loop window of the cell's traffic at that rate. The
benchmark's runs never search for a rate: the knee found here is written
into the traffic file as a number once. Prints one JSON line per rate.
"""

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# a queue that grows: the last quarter's median latency is more than this
# many times the first quarter's
GROWTH = 1.25


def main(argv=None) -> int:
    import argparse

    sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                    if p not in sys.path]
    import jax

    from bench import harness, load, spec
    from repro.serve.loop import Rejected

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load(ROOT, args.workload)
    try:
        harness.require_accelerator(cell.chips)
    except harness.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache(cell.config)
    compiles = harness.CompileClock().register()
    system = harness.prepare(cell, args.seed, args.seconds, compiles)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            front, _probe = system.front(jax.profiler.TraceAnnotation)
            try:
                offsets = load.poisson_schedule(rate, args.seconds,
                                                args.seed)
                c0 = compiles.count
                win = load.open_loop(front, system.request, system.order,
                                     offsets, Rejected)
            finally:
                front.stop(drain=True)
            done = [r for r in win.records if r.answered]
            lat = sorted(r.done - r.due for r in done)
            quarter = max(len(done) // 4, 1)
            first = statistics.median(r.done - r.due for r in done[:quarter])
            last = statistics.median(r.done - r.due for r in done[-quarter:])
            # throughput over the span of the schedule, so that the last
            # request's own latency does not count against the rate
            span = float(offsets[-1]) + 1.0 / rate
            achieved = len(done) / span
            print(json.dumps({
                "rate": rate, "offered": len(win.records),
                "answered": len(done), "achieved_qps": achieved,
                "p50_ms": 1e3 * lat[len(lat) // 2],
                "p95_ms": harness.p95(win),
                "first_quarter_ms": 1e3 * first,
                "last_quarter_ms": 1e3 * last,
                "compiles": compiles.count - c0,
                "sustained": (len(done) >= 0.97 * len(win.records)
                              and last <= GROWTH * first),
            }), flush=True)
            time.sleep(1.0)
    finally:
        system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
