"""The benchmark's own tests run on the CPU, with the program's Pallas
kernels in interpret mode: `python -m pytest bench/tests`."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["REPRO_KERNEL_INTERPRET"] = "1"

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
