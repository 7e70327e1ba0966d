"""Whole fits back to back in a closed loop.

The configuration names a fixed set of collections (`data_seeds`); each is
made by the benchmark's generator and fitted with the key of its own seed.
The run's `--seed` only orders them: every run does the same fits, in
another order. ALID's seed sampling makes a fit's rounds, and so its time,
differ from collection to collection by up to a quarter, and a seed that
changed the collections would move `fit_pts_per_s` by as much.

Set-up fits each collection once, which compiles and warms every program
the window runs (a collection with its key takes the same path each time).
The window then makes whole passes, each fitting every collection once in
the seed's order, until `seconds` have passed; a pass that starts inside
it runs to its end and counts, so every run fits each collection equally
often whatever the fits' speed. `fit_pts_per_s` is the points of the
completed fits over the time from the window's start to the end of the
last pass. Traced, the window is one whole fit.

Every fit of the window must equal its collection's set-up fit bit for
bit, and every collection fitted in the window is held to the plain
reference (`reference.fit_numbers`).
"""

from __future__ import annotations

import time

import numpy as np

import deploy
import reference
from harness import Check, DriverResult, checks_from, prng_key


def alid_config(conf: dict):
    from repro.core.alid import ALIDConfig, EngineSpec
    from repro.lsh.pstable import LSHParams
    lsh, alid = conf["lsh"], conf["alid"]
    return ALIDConfig(
        a_cap=alid["a_cap"], delta=alid["delta"],
        seeds_per_round=alid["seeds_per_round"],
        max_rounds=alid["max_rounds"], density_min=alid["density_min"],
        lsh=LSHParams(n_tables=lsh["n_tables"],
                      n_projections=lsh["n_projections"],
                      seg_len=lsh["seg_len"], probe=lsh["probe"]),
        spec=EngineSpec(engine=conf["engine"], dtype=conf["dtype"]))


def same(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in
               ((a.labels, b.labels), (a.densities, b.densities),
                (a.support_idx, b.support_idx), (a.support_w, b.support_w),
                (a.support_v, b.support_v))) and a.k == b.k


def worst(readings: list[dict], limits: dict) -> list[Check]:
    """One check per compared number: its worst reading over the
    collections fitted in the window."""
    worst_of = {}
    for name, spec in limits.items():
        vals = [r.get(name, float("nan")) for r in readings]
        worst_of[name] = (max if spec["op"] == "<=" else min)(vals)
    return checks_from(worst_of, limits)


def run(ctx) -> DriverResult:
    from repro.core.engine import fit

    conf = ctx.config
    seeds = conf["data_seeds"]
    deps = [deploy.deployment(conf["n"], conf["d"], conf["clusters"],
                              conf["member_share"], s) for s in seeds]
    keys = [prng_key(s) for s in seeds]
    order = np.random.default_rng(ctx.seed).permutation(len(seeds))
    cfg = alid_config(conf)
    n = conf["n"]
    with ctx.span("bench.warmup"):
        warm = [fit(d.points, cfg, k) for d, k in zip(deps, keys)]
    ctx.log("warm-up fits: rounds " + str([w.n_rounds for w in warm]))

    fits, ends = [], []
    t0 = ctx.open_window()
    if ctx.tracing:
        i = int(order[0])
        with ctx.traced(), ctx.span("bench.fit"):
            fits.append((i, fit(deps[i].points, cfg, keys[i])))
        ends.append(time.perf_counter())
    else:
        while time.perf_counter() - t0 < ctx.seconds:
            for i in map(int, order):
                with ctx.span("bench.fit"):
                    fits.append((i, fit(deps[i].points, cfg, keys[i])))
                ends.append(time.perf_counter())
    ctx.close_window()
    ctx.log(f"{len(fits)} fits in {ends[-1] - t0:.3f} s")

    differing = sum(not same(res, warm[i]) for i, res in fits)
    first = {}
    for i, res in fits:
        first.setdefault(i, res)
    used = sorted(first)
    readings = [reference.fit_numbers(deps[i].points, deps[i].labels,
                                      first[i],
                                      deploy.laplacian_k(deps[i].points))
                for i in used]
    rounds = sum(res.n_rounds for _, res in fits)
    return DriverResult(
        attempted=len(fits), failed=0,
        end_to_end={"fit_pts_per_s": n * len(fits) / (ends[-1] - t0)},
        counters={"rounds": rounds, "fits": len(fits)},
        checks=[Check("fits_differing", differing, 0, "<=")]
        + worst(readings, conf["checks"]),
        notes={"order": [seeds[i] for i in order],
               "passes": len(fits) / len(order),
               "fit_s": [e - s for s, e in zip([t0] + ends[:-1], ends)],
               "rounds": [res.n_rounds for _, res in fits],
               "readings": dict(zip((seeds[i] for i in used), readings))})
