#!/usr/bin/env python3
"""Knee sweep of a serving configuration on the chip, in one process.

    python3 bench/sweep.py --config blobs1m-d128 --seed 5 --seconds 8 \\
        --rates 1000,2000,4000,8000

Builds the tenant once, then offers each rate for `--seconds` of Poisson
arrivals (traffic parameters of `--traffic`) and prints one JSON line per
rate: p50 and p95 latency, the share of requests answered inside the
window, the backlog when the last request was due, and how late the
generator ran. The knee is the highest rate at which answers keep up and
the backlog does not grow; the serving cells' rates are fixed from it by
hand (PERF.md records the sweep).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="blobs1m-d128")
    ap.add_argument("--traffic", default="open.r80")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()

    import contextlib

    import numpy as np

    import deploy
    import harness
    import openloop
    from drivers.open_loop import TENANT, tenant
    from repro.serve import ClusterServer

    harness.setup_jax()
    harness.require_chips(1)
    with open(os.path.join(BENCH, "configs", args.config + ".json")) as f:
        conf = json.load(f)
    with open(os.path.join(BENCH, "traffic", args.traffic + ".json")) as f:
        trf = json.load(f)
    rng = np.random.default_rng([args.seed, 2])
    dep, clus, thr = tenant(conf, args.seed)
    rates = [float(r) for r in args.rates.split(",")]
    dues = [openloop.poisson_arrivals(r, args.seconds, rng) for r in rates]
    queries = deploy.fresh_queries(dep, max(len(d) for d in dues),
                                   trf["member_share"], conf["noise_range"],
                                   rng)
    del dep
    slots = trf["batch_slots"]
    with ClusterServer(batch_slots=slots, queue_limit=len(queries) + 1,
                       policy="reject") as server:
        server.add_tenant(TENANT, clus, threshold=thr)
        for f in [server.submit(q, tenant=TENANT) for q in queries[:slots]]:
            f.result(timeout=600)
        print(f"# set-up {time.perf_counter() - T_START:.3f} s",
              flush=True)
        for rate, due in zip(rates, dues):
            before = server.stats.snapshot()
            res = openloop.run(lambda q: server.submit(q, tenant=TENANT),
                               queries, due, server.queue_depth,
                               trf["drain_s"],
                               lambda name: contextlib.nullcontext())
            after = server.stats.snapshot()
            ok = ~res.failed
            lat = (res.done_s - res.due_s)[ok] * 1e3
            batches = after["batches"] - before["batches"]
            print(json.dumps({
                "rate_hz": rate, "requests": len(due),
                "failed": int(res.failed.sum()),
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "answered_in_window": float(np.mean(
                    res.done_s[ok] <= args.seconds)),
                "backlog_at_close": res.backlog_at_close,
                "generator_late_ms": openloop.lateness_ms(res),
                "occupancy": (after["slots_filled"] - before["slots_filled"])
                / max(batches, 1) / slots,
                "batches": batches}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
