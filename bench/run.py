"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` and found by name under ``bench/`` (``bench/spec.py``).
Exits 3 and prints no result when JAX finds no TPU, or fewer chips than
the cell asks for. The last line of standard output is the result, one
JSON object; the numbers that decide ``correct`` and their limits are
the last lines of standard error.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None, root: str = ROOT) -> int:
    sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                    if p not in sys.path]
    from bench import harness

    return harness.main(sys.argv[1:] if argv is None else argv, root,
                        T_START)


if __name__ == "__main__":
    sys.exit(main())
