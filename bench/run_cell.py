"""Run one benchmark cell and print its result as the last line.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
BENCHMARK.json at the root of the checkout. The run refuses any backend
but a TPU with as many chips as the cell asks for (exit 2, no result).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    from bench import harness
    harness.emit(harness.run(a.workload, a.seed, a.seconds, bool(a.trace),
                             t_start=T_START))


if __name__ == "__main__":
    main()
