# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark runner: every paper table/figure + kernel micro-benches + the
roofline summary (reads dry-run artifacts if present).

    PYTHONPATH=src python -m benchmarks.run          # quick (CI-sized)
    PYTHONPATH=src python -m benchmarks.run --full
    PYTHONPATH=src python -m benchmarks.run --device-report
                                        # kernel + roofline device-perf only
"""

import argparse
import json
import os
import time


def device_report() -> None:
    """The merged device-perf report: the per-op half (kernel_bench's
    fused/unfused timings + analytic flops/bytes/roofline placement, written
    to BENCH_kernels.json v2) and the per-cell half (roofline.py's program
    rows from dry-run artifacts, when present), one JSON."""
    from benchmarks import kernel_bench
    from benchmarks.roofline import DRYRUN_KIND, build_rows, peaks

    kernel_bench.main(quick=True)
    with open("BENCH_kernels.json") as f:
        kernels = json.load(f)
    out = {"model": {"device_kind": DRYRUN_KIND, **peaks(DRYRUN_KIND)},
           "kernels": kernels,
           "cells": build_rows("single")}   # [] when no dry-run artifacts
    path = os.path.join("experiments", "device_perf.json")
    os.makedirs("experiments", exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    n_warn = len(kernels.get("warnings", []))
    print(f"device_report/written,0,{path};ops={len(kernels['fused_ops'])};"
          f"warnings={n_warn}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--device-report", action="store_true",
                    help="only the kernel + roofline device-perf report")
    args = ap.parse_args()
    quick = not args.full

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.device_report:
        print("name,us_per_call,derived")
        device_report()
        return

    print("name,us_per_call,derived")
    t0 = time.time()

    from benchmarks import (fig6_sparsity, fig7_scalability, fig11_noise,
                            kernel_bench, mem_footprint, online_updates,
                            resilience, serving_latency, streamed_throughput,
                            table2_speedup)
    for mod in (fig6_sparsity, fig7_scalability, table2_speedup, fig11_noise,
                mem_footprint, streamed_throughput, serving_latency,
                online_updates, resilience, kernel_bench):
        mod.main(quick=quick)     # a failing phase fails the run

    # roofline summary (dominant-term counts) if dry-run artifacts exist
    from benchmarks.roofline import build_rows
    rows = [r for r in build_rows("single") if r["status"] == "ok"]
    if rows:
        from collections import Counter
        doms = Counter(r["dominant"] for r in rows)
        best = max(rows, key=lambda r: r["roofline_frac"])
        print(f"roofline/summary,0,cells={len(rows)};"
              + ";".join(f"{k}_bound={v}" for k, v in doms.items())
              + f";best_frac={best['roofline_frac']:.2f}({best['cell']})")

    print(f"total/wall,{(time.time()-t0)*1e6:.0f},done")


if __name__ == "__main__":
    main()
