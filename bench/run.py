#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload fit.blobs100k --seed 7 --seconds 20 \\
        --trace 0

Loads and warms up the cell (set-up, `setup_s`), measures for `--seconds`,
checks what the measured window produced against the plain reference, and
prints one JSON object as the last line of stdout (the compared numbers
with their limits are the last lines of stderr). `--trace 1` measures one
short traced window instead and reports the cell's per-layer metrics.
Exits 3, printing no result, when there is no TPU or fewer chips than the
cell asks for. Cells, configurations and metrics are in BENCHMARK.json;
bench/harness.py says where each one's files are.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


if __name__ == "__main__":
    import harness
    sys.exit(harness.main(parse(), T_START))
