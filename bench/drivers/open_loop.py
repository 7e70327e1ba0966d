"""Assignment serving under an open loop of Poisson arrivals.

Set-up makes the deployment from the seed and builds the tenant from its
planted clusters (`deploy.planted_supports`, densities and k by the
benchmark's own plain code) with an acceptance threshold that turns away
the configuration's share of a sample of fresh noise queries
(`deploy.noise_threshold`), starts a `ClusterServer` with the traffic's
batch slots and a queue that never refuses at the offered load, uploads
the tenant and warms the one batch shape the server launches. The queries
(fresh draws from the deployment's generator) and their due times are made
from the seed before the window opens. The window submits each query at
its due time and waits for every answer; latency runs from the due time to
the answer. Traced, the window is `trace_seconds` of the same traffic.

After the window the server is closed and its tenant freed; a sample of
the answered requests, drawn from the seed, is scored by the plain
reference (`reference.support_scores`), each served label is held to the
reference's answer (`reference.answer_gaps`), and the share of the
sample that was given a cluster is held to the range the configuration
states.
"""

from __future__ import annotations

import numpy as np

import deploy
import openloop
import reference
from harness import Check, DriverResult, checks_from

TENANT = "blobs"


def tenant(conf: dict, seed: int):
    """(the deployment, the tenant's `Clustering` built from it, its
    acceptance threshold)."""
    from repro.core.alid import Clustering
    dep = deploy.deployment(conf["n"], conf["d"], conf["clusters"],
                            conf["member_share"], seed)
    k = deploy.laplacian_k(dep.points)
    idx, w, v = deploy.planted_supports(dep, conf["cap"])
    dens = deploy.support_densities(v, w, k).astype(np.float32)
    clus = Clustering(labels=dep.labels, densities=dens, n_rounds=0, k=k,
                      support_idx=idx, support_w=w, support_v=v)
    bar = conf["acceptance"]
    thr = deploy.noise_threshold(dep, v, w, dens, k, bar["noise_turned_away"],
                                 bar["calibration_queries"],
                                 conf["noise_range"],
                                 np.random.default_rng([seed, 4]))
    return dep, clus, thr


def answer_checks(served, scores, dens, thr: float, conf: dict,
                  unanswered: int = 0) -> list[Check]:
    """The compared numbers of served answers against the reference's
    scores of the same queries: the worst answer gap, and the share of
    answers that name a cluster (the members and the part of the noise
    that the acceptance bar lets through)."""
    served = np.asarray(served)
    gaps = reference.answer_gaps(served, scores, dens, thr)
    readings = {"answer_gap": float(gaps.max(initial=0.0)),
                "labelled_share": (float(np.mean(served >= 0))
                                   if served.size else float("nan"))}
    return ([Check("unanswered", unanswered, 0, "<=")]
            + checks_from(readings, conf["checks"]))


def run(ctx) -> DriverResult:
    from repro.serve import ClusterServer

    conf, trf = ctx.config, ctx.traffic
    rng = np.random.default_rng([ctx.seed, 1])
    dep, clus, thr = tenant(conf, ctx.seed)
    seconds = trf["trace_seconds"] if ctx.tracing else ctx.seconds
    due = openloop.poisson_arrivals(trf["rate_hz"], seconds, rng)
    warm_n = 4 * trf["batch_slots"]
    queries = deploy.fresh_queries(dep, warm_n + len(due),
                                   trf["member_share"], conf["noise_range"],
                                   rng)
    del dep
    server = ClusterServer(batch_slots=trf["batch_slots"],
                           queue_limit=warm_n + len(due) + 1,
                           policy="reject")
    try:
        server.add_tenant(TENANT, clus, threshold=thr)
        with ctx.span("bench.warmup"):
            for i in range(0, warm_n, trf["batch_slots"]):
                futs = [server.submit(q, tenant=TENANT)
                        for q in queries[i:i + trf["batch_slots"]]]
                for f in futs:
                    f.result(timeout=600)
            server.submit(queries[0], tenant=TENANT).result(timeout=600)
        before = server.stats.snapshot()

        def submit(q):
            return server.submit(q, tenant=TENANT)

        ctx.open_window()
        if ctx.tracing:
            with ctx.traced():
                res = openloop.run(submit, queries[warm_n:], due,
                                   server.queue_depth, trf["drain_s"],
                                   ctx.span)
        else:
            res = openloop.run(submit, queries[warm_n:], due,
                               server.queue_depth, trf["drain_s"], ctx.span)
        ctx.close_window()
        after = server.stats.snapshot()
    finally:
        server.close(drain=False, timeout=60)
    del server

    window = {k: after[k] - before[k] for k in after}
    ok = ~res.failed
    lat_ms = (res.done_s - res.due_s)[ok] * 1e3
    pick = np.sort(rng.choice(np.flatnonzero(ok),
                              size=min(trf["check_sample"], int(ok.sum())),
                              replace=False))
    q = queries[warm_n:][pick]
    scores = reference.support_scores(q, clus.support_v, clus.support_w,
                                      clus.k)
    checks = answer_checks(res.answers[pick], scores, clus.densities, thr,
                           conf, unanswered=int(res.failed.sum()))
    batches = max(window["batches"], 1)
    served = max(window["served"], 1)
    e2e = {}
    if lat_ms.size:
        e2e = {"serve_p50_ms": float(np.percentile(lat_ms, 50)),
               "serve_p95_ms": float(np.percentile(lat_ms, 95))}
    return DriverResult(
        attempted=len(due), failed=int(res.failed.sum()),
        end_to_end=e2e,
        counters={"occupancy": window["slots_filled"]
                  / (batches * trf["batch_slots"]),
                  "queue_wait_ms": window["queue_wait_s"] / served * 1e3,
                  "batches": window["batches"],
                  "batch_slots": trf["batch_slots"],
                  "clusters": clus.support_v.shape[0],
                  "cap": clus.support_v.shape[1],
                  "d": clus.support_v.shape[2]},
        checks=checks,
        notes={"latency_ms": {"p50": e2e.get("serve_p50_ms"),
                              "p95": e2e.get("serve_p95_ms")},
               "generator_late_ms": openloop.lateness_ms(res),
               "generator_stalls": openloop.stalls(res),
               "backlog_at_close": res.backlog_at_close,
               "answered_in_window": float(np.mean(
                   res.done_s[ok] <= seconds)) if ok.any() else 0.0,
               "batches": window["batches"],
               "labelled_share": float(np.mean(res.answers[ok] >= 0))
               if ok.any() else 0.0,
               "checked": int(pick.size),
               "threshold": thr})
