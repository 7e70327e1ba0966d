"""Whole out-of-core fits back to back in a closed loop, from memmaps.

The streamed counterpart of `closed_fits`, with its collections, order,
window and checks. Set-up writes each collection once as a `.npy` in a
temporary directory of the run's own and fits it through a `MemmapSource`
with the configuration's streamed `EngineSpec` (shard count, prefetch
depth, host cache budget), so the points are never handed to the program
in memory; the engine's scratch memmap lies in the same directory, which
the driver removes at the end. The set-up fits compile and warm every
program the window runs.

The window fits the collections in the seed's order until `seconds` have
passed; a fit that starts inside it runs to its end and counts. Traced,
the window is one whole fit. Every fit of the window must equal its
collection's set-up fit bit for bit, and every collection fitted in the
window is held to the plain reference (`reference.fit_numbers`).
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

import deploy
import reference
from harness import BENCH, Check, DriverResult, load_module, prng_key

closed_fits = load_module(os.path.join(BENCH, "drivers", "closed_fits.py"),
                          "driver_closed_fits")


def stream_config(conf: dict, scratch_dir: str):
    """`closed_fits.alid_config` with the configuration's streamed
    `EngineSpec` fields and the scratch memmap in `scratch_dir`."""
    stream = conf["stream"]
    cfg = closed_fits.alid_config(conf)
    return cfg._replace(spec=cfg.spec._replace(
        n_shards=stream["n_shards"], prefetch_depth=stream["prefetch_depth"],
        cache_bytes=stream["cache_bytes"], scratch_dir=scratch_dir))


def run(ctx) -> DriverResult:
    from repro.core.engine import fit, make_engine
    from repro.core.source import MemmapSource

    conf = ctx.config
    seeds = conf["data_seeds"]
    keys = [prng_key(s) for s in seeds]
    order = np.random.default_rng(ctx.seed).permutation(len(seeds))
    n = conf["n"]
    work_dir = tempfile.mkdtemp(prefix="bench_stream_")
    try:
        cfg = stream_config(conf, work_dir)
        deps, paths = [], []
        for s in seeds:
            deps.append(deploy.deployment(n, conf["d"], conf["clusters"],
                                          conf["member_share"], s))
            paths.append(os.path.join(work_dir, f"points_{s}.npy"))
            np.save(paths[-1], deps[-1].points)
        stats: list[dict] = []

        def fit_one(i: int):
            engine = make_engine(cfg.spec)
            try:
                res = fit(MemmapSource(paths[i]), cfg, keys[i], engine=engine)
            finally:
                engine.close()
            stats.append(engine.stats.snapshot())
            return res

        with ctx.span("bench.warmup"):
            warm = [fit_one(i) for i in range(len(seeds))]
        ctx.log("warm-up fits: rounds " + str([w.n_rounds for w in warm]))

        fits, ends = [], []
        del stats[:]
        t0 = ctx.open_window()
        if ctx.tracing:
            i = int(order[0])
            with ctx.traced(), ctx.span("bench.fit"):
                fits.append((i, fit_one(i)))
            ends.append(time.perf_counter())
        else:
            while time.perf_counter() - t0 < ctx.seconds:
                for i in map(int, order):
                    with ctx.span("bench.fit"):
                        fits.append((i, fit_one(i)))
                    ends.append(time.perf_counter())
        ctx.close_window()
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    ctx.log(f"{len(fits)} fits in {ends[-1] - t0:.3f} s")

    differing = sum(not closed_fits.same(res, warm[i]) for i, res in fits)
    first = {}
    for i, res in fits:
        first.setdefault(i, res)
    used = sorted(first)
    readings = [reference.fit_numbers(deps[i].points, deps[i].labels,
                                      first[i],
                                      deploy.laplacian_k(deps[i].points))
                for i in used]
    return DriverResult(
        attempted=len(fits), failed=0,
        end_to_end={"fit_pts_per_s": n * len(fits) / (ends[-1] - t0)},
        counters={"rounds": sum(res.n_rounds for _, res in fits),
                  "fits": len(fits)},
        checks=[Check("fits_differing", differing, 0, "<=")]
        + closed_fits.worst(readings, conf["checks"]),
        notes={"order": [seeds[i] for i in order],
               "fit_s": [e - s for s, e in zip([t0] + ends[:-1], ends)],
               "rounds": [res.n_rounds for _, res in fits],
               "pipeline": stats,
               "readings": dict(zip((seeds[i] for i in used), readings))})
