"""Benchmark entry point: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale small|default|large]
                                            [--only fig3,fig8,...]
    PYTHONPATH=src python -m benchmarks.run --snapshot           # perf
        trajectory: writes the current snapshot (benchmarks/snapshot.py
        SNAPSHOT_NAME, e.g. BENCH_pr5.json; override the path with
        --out) at the repo root — kernel µs, bytes-read, queries/s and
        the out-of-core serving rows at the default scale
    PYTHONPATH=src python -m benchmarks.run --snapshot --smoke   # the
        scripts/verify.sh gate: compile+run every snapshot path once at
        the small scale, write nothing

Prints ``name,us_per_call,derived`` CSV lines (harness contract) and
writes JSON rows under experiments/bench/."""

from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

SUITES = {
    "fig2_indexing": "benchmarks.bench_indexing",
    "fig3_query_memory": "benchmarks.bench_query_memory",
    "fig4_query_disk": "benchmarks.bench_query_disk",
    "fig5_accuracy_measures": "benchmarks.bench_accuracy_measures",
    "fig6_best_methods": "benchmarks.bench_best_methods",
    "fig7_effect_k": "benchmarks.bench_effect_k",
    "fig8_delta_epsilon": "benchmarks.bench_delta_epsilon",
    "kernels": "benchmarks.bench_kernels",
    "roofline": "benchmarks.bench_roofline",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default=None,
                    choices=["small", "default", "large"],
                    help="bench scale (figure suites default to small; "
                         "--snapshot defaults to default)")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite keys (substring match)")
    ap.add_argument("--out", default=None,
                    help="figure suites: JSON output dir (default "
                         "experiments/bench). --snapshot: the snapshot "
                         "file path (default: snapshot.SNAPSHOT_NAME "
                         "at the repo root, e.g. --out BENCH_pr5.json)")
    ap.add_argument("--snapshot", action="store_true",
                    help="write the perf-trajectory snapshot "
                         "(snapshot.SNAPSHOT_NAME or --out) at the "
                         "repo root instead of running the figure "
                         "suites")
    ap.add_argument("--smoke", action="store_true",
                    help="with --snapshot: compile+run once at the "
                         "small scale, write nothing (verify.sh gate)")
    args = ap.parse_args()
    from repro.runtime import enable_compile_cache

    enable_compile_cache()

    if args.smoke and not args.snapshot:
        ap.error("--smoke only applies to --snapshot")
    if args.snapshot:
        if args.only is not None:
            ap.error("--only does not apply to --snapshot")
        if args.smoke and args.out is not None:
            ap.error("--out does not apply to --smoke (writes nothing)")
        from . import snapshot

        out_path = None
        if args.out is not None:
            out_path = args.out if os.path.dirname(args.out) \
                else snapshot._repo_root_path(args.out)
        # explicit --scale is honored; --smoke shrinks the default
        scale = args.scale or ("small" if args.smoke else "default")
        snapshot.run_snapshot(scale=scale, smoke=args.smoke,
                              out_path=out_path)
        return

    args.scale = args.scale or "small"
    args.out = args.out or "experiments/bench"

    import importlib

    failures = 0
    print("name,us_per_call,derived")
    for key, modname in SUITES.items():
        if args.only and not any(tok in key
                                 for tok in args.only.split(",")):
            continue
        t0 = time.perf_counter()
        try:
            mod = importlib.import_module(modname)
            mod.run(args.scale, out_dir=args.out)
            print(f"# {key} done in {time.perf_counter() - t0:.1f}s",
                  file=sys.stderr)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# {key} FAILED", file=sys.stderr)
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
