#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip at the cell's own size:
the program's compared numbers over many seeds, the control's, and the
faults'.

    python3 bench/control.py --workload fit.blobs100k --seeds 1,2,3 \\
        --control-seeds 1,2,3

The control is what a lower precision gives, in the program's place:

  fit cells      the program's own bfloat16 storage path
                 (EngineSpec(dtype="bfloat16")), checked like the cell;
  serving cells  the plain reference with its inputs rounded to bfloat16
                 and contracted in one bfloat16 pass, answering as many
                 fresh queries of the cell's mix as a run checks.

Serving cells also read two faults of the acceptance bar, in the
program's place: the float32 reference answering with the bar ignored
(every query gets its best cluster) and with the bar doubled (2 x the
threshold, as densities read twice too large would give).

Prints one JSON line per (side, seed) with the numbers the cell compares,
each check as the cell's own comparison makes it (`harness.Check` with the
configuration's limits), and whether that comes out `correct`. For a
serving cell the program's side is the cell run itself (`bench/run.py`);
this script gives the control's and the faults' sides.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def fit_rows(cell, seeds, control_seeds):
    import deploy
    import harness
    import reference
    from drivers.closed_fits import alid_config, worst
    from repro.core.engine import fit

    for side, dtype, chosen in (("program", cell.config["dtype"], seeds),
                                ("control", "bfloat16", control_seeds)):
        cfg = alid_config(dict(cell.config, dtype=dtype))
        for seed in chosen:
            conf = cell.config
            dep = deploy.deployment(conf["n"], conf["d"], conf["clusters"],
                                    conf["member_share"], seed)
            t = time.perf_counter()
            res = fit(dep.points, cfg, harness.prng_key(seed))
            wall = time.perf_counter() - t
            nums = reference.fit_numbers(dep.points, dep.labels, res,
                                         deploy.laplacian_k(dep.points))
            checks = worst([nums], conf["checks"])
            yield dict(side=side, seed=seed, dtype=dtype, fit_s=wall,
                       rounds=res.n_rounds, **nums,
                       **verdict(checks))


def serve_rows(cell, control_seeds):
    import numpy as np

    import deploy
    import reference
    from drivers.open_loop import answer_checks, tenant

    conf, trf = cell.config, cell.traffic
    for seed in control_seeds:
        dep, clus, thr = tenant(conf, seed)
        q = deploy.fresh_queries(dep, trf["check_sample"],
                                 trf["member_share"], conf["noise_range"],
                                 np.random.default_rng([seed, 3]))
        del dep
        args = (clus.support_v, clus.support_w, clus.k)
        ref = reference.support_scores(q, *args)
        low = reference.support_scores(q, *args, dtype="bfloat16")
        dens = clus.densities
        for side, scores, bar in (("control", low, thr),
                                  ("fault.bar_ignored", ref, 0.0),
                                  ("fault.bar_doubled", ref, 2 * thr)):
            served = reference.labels_from_scores(scores, dens, bar)
            checks = answer_checks(served, ref, dens, thr, conf)
            gaps = reference.answer_gaps(served, ref, dens, thr)
            yield dict(side=side, seed=seed, checked=len(q), threshold=thr,
                       wrong=int((gaps > 0).sum()),
                       **{c.name: c.value for c in checks},
                       **verdict(checks))


def verdict(checks) -> dict:
    return {"checks_failed": [c.name for c in checks if not c.ok],
            "correct": all(c.ok for c in checks)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", required=True)
    args = ap.parse_args()

    import harness
    harness.setup_jax()
    harness.require_chips(1)
    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    rows = (fit_rows(cell, seeds, control)
            if cell.traffic["driver"] == "closed_fits"
            else serve_rows(cell, control))
    for row in rows:
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
