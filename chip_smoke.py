#!/usr/bin/env python3
"""On-chip smoke test: ALID's main path, end to end, on a TPU.

    python chip_smoke.py             # one chip: phases 1-5
    python chip_smoke.py --chips 4   # four chips: the PALID mesh fits only

The deployment is the paper's SIFT width (d = 128, f32 storage) through the
synthetic generator `run_palid` fits: 200 planted clusters holding 40% of
the seeded points, the rest uniform noise. The fit and the server run it at
`run_palid --n 1000000 --d 128 --clusters 200` (2,000-point clusters,
a_cap = 2032, LID capacity 2160), with the points resident on the device.
Two phases run the same generator at a smaller n, each for a stated reason:

  * the quality gate (>= 150 of 200 planted clusters found, AVG-F >= 0.75)
    applies at n = 100,000, the largest n of this generator at which ALID
    meets it. Above ~500-point clusters ALID's supports stop at a dense
    core of each planted blob and AVG-F falls (0.60 at n = 200,000 in a
    CPU fit; PERF.md), so at 1,000,000 the smoke checks that the fit
    completes and reports its figures;
  * the streamed engine runs at n = 30,000: at 1,000,000 one of its rounds
    takes ~100 s on a v5e, so a fit does not fit the run's time limit, and
    above ~40,000 points its sharded retrieval samples other bucket members
    than the replicated engine does, so matched agreement falls below 99%.

One chip:
  1. device     a TPU is attached and every kernel op resolves to Pallas;
  2. fit        `fit` on the replicated engine at n = 1,000,000 completes:
                every point labelled, >= 1 cluster, each cluster's density
                finite and >= density_min, its support weights a simplex;
                planted clusters found and AVG-F are reported;
  3. serving    a `ClusterServer` with phase 2's `Clustering` as tenant:
                256 submitted dataset rows all resolve, with the labels of
                `Clustering.predict(..., backend="ref")` except rows whose
                best two cluster scores are within 1e-5 relative;
  4. reference  n = 100,000 fitted on Pallas and on the pure-jnp ref
                backend: >= 99% of points agree after matching cluster ids
                by overlap, and the Pallas fit passes the quality gate;
  5. streamed   n = 30,000 from a .npy memmap through the streamed engine
                (16 shards, background reader, device slot ring): >= 99%
                matched agreement with the replicated fit of those points.

Four chips (`--chips 4`, n = 30,000 for the agreement reason above): the
one-device replicated fit, then `MeshEngine` over a 4-device mesh with
replicated data (n_shards=0) and with the mesh-placed store (n_shards=4).
Each mesh fit must agree with the one-device fit on >= 99% of points after
matching, and a mesh round must place its seeds on all four devices.

Everything runs in this one process. Each phase prints its wall time
(compilation included) and its figures; a failed check exits non-zero.
The last line of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_FIT, N_REF, N_PART = 1_000_000, 100_000, 30_000
D, CLUSTERS, SEED = 128, 200, 0
MIN_FOUND, MIN_AVGF, MIN_AGREE = 150, 0.75, 0.99
N_QUERIES, TIE_RTOL, SCORE_ROWS = 256, 1e-5, 32


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    log(("ok   " if ok else "FAIL ") + msg)
    if not ok:
        raise SmokeFailure(msg)


class phase:
    """Times one phase, compilation included, and logs its wall time."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        log(f"phase={self.name} start")
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall_s = time.perf_counter() - self.t0
        log(f"phase={self.name} wall_s={self.wall_s:.2f}"
            + (" (failed)" if exc[0] else ""))


def deployment(n: int, spec):
    """(blobs, ALIDConfig) of run_palid's synthetic workload at n x D."""
    from repro.core.alid import ALIDConfig
    from repro.launch.run_palid import synthetic_deployment
    blobs, lshp, a_cap = synthetic_deployment(n, D, CLUSTERS, SEED)
    cfg = ALIDConfig(a_cap=a_cap, delta=128, lsh=lshp, seeds_per_round=32,
                     max_rounds=64, spec=spec)
    log(f"deployment: n={n} d={D} a_cap={cfg.a_cap} cap={cfg.cap} "
        f"seg_len={lshp.seg_len:.3f}")
    return blobs, cfg


def timed_fit(name: str, data, cfg, **kw):
    import jax

    from repro.core.engine import fit
    t0 = time.perf_counter()
    res = fit(data, cfg, jax.random.PRNGKey(SEED), **kw)
    log(f"{name}: fit wall_s={time.perf_counter() - t0:.2f}")
    return res


def fit_summary(name: str, blobs, res) -> dict:
    from repro.utils import best_f1_per_cluster
    best = best_f1_per_cluster(blobs.labels, res.labels)
    out = dict(clusters=res.n_clusters, rounds=res.n_rounds,
               members=int((res.labels >= 0).sum()),
               planted_found=int((best >= 0.5).sum()),
               avg_f=float(best.mean()))
    log(f"{name}: " + " ".join(f"{k}={v}" for k, v in out.items()))
    return out


def check_quality(name: str, s: dict) -> None:
    check(s["planted_found"] >= MIN_FOUND,
          f"{name}: planted clusters found {s['planted_found']} >= "
          f"{MIN_FOUND}")
    check(s["avg_f"] >= MIN_AVGF, f"{name}: AVG-F {s['avg_f']:.4f} >= "
          f"{MIN_AVGF}")


def check_complete(name: str, res, n: int, cfg) -> None:
    """The fit ran to an end and its result is well formed."""
    labels, c = np.asarray(res.labels), res.n_clusters
    check(labels.shape == (n,), f"{name}: labels shape {labels.shape} == "
          f"({n},)")
    check(c >= 1, f"{name}: {c} clusters >= 1")
    counts = np.bincount(labels[labels >= 0], minlength=c)
    check(labels.min() >= -1 and labels.max() < c and counts.min() >= 2,
          f"{name}: labels in [-1, {c}), every cluster >= 2 members")
    dens = np.asarray(res.densities)
    check(bool(np.isfinite(dens).all() and (dens >= cfg.density_min).all()),
          f"{name}: densities finite and >= {cfg.density_min} (min "
          f"{dens.min():.4f})")
    w = np.asarray(res.support_w)
    check(bool(np.isfinite(w).all() and (w >= 0).all()
               and np.allclose(w.sum(axis=1), 1.0, atol=1e-4)
               and np.isfinite(res.support_v).all()),
          f"{name}: support weights finite simplices, support rows finite")


def agreement(name: str, a, b) -> float:
    from repro.utils import matched_agreement
    agree = matched_agreement(a, b)
    check(agree >= MIN_AGREE,
          f"{name}: matched agreement {agree:.6f} >= {MIN_AGREE}")
    return agree


def phase_device() -> None:
    import jax

    from repro.kernels import ops
    dev = jax.devices()[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())}")
    check(dev.platform == "tpu", f"platform {dev.platform!r} is 'tpu'")
    mode = ops.resolve_backend("auto")
    check(mode == "pallas",
          f"kernel backend resolves to {mode!r} == 'pallas' (env "
          f"REPRO_KERNEL_BACKEND={os.environ.get('REPRO_KERNEL_BACKEND')!r}"
          f" REPRO_KERNEL_INTERPRET="
          f"{os.environ.get('REPRO_KERNEL_INTERPRET')!r})")


def phase_fit():
    from repro.core.alid import EngineSpec
    blobs, cfg = deployment(N_FIT, EngineSpec())
    res = timed_fit("fit", blobs.points, cfg)
    fit_summary("fit", blobs, res)
    check_complete("fit", res, N_FIT, cfg)
    return blobs, res


def phase_reference() -> None:
    from repro.core.alid import EngineSpec
    blobs, cfg = deployment(N_REF, EngineSpec())
    base = timed_fit("reference/pallas", blobs.points, cfg)
    check_quality("reference/pallas",
                  fit_summary("reference/pallas", blobs, base))
    ref_cfg = cfg._replace(spec=cfg.spec._replace(backend="ref"))
    res = timed_fit("reference/ref", blobs.points, ref_cfg)
    fit_summary("reference/ref", blobs, res)
    agreement("reference: pallas vs ref", base.labels, res.labels)


def phase_streamed() -> None:
    from repro.core.alid import EngineSpec
    from repro.core.source import MemmapSource
    blobs, cfg = deployment(N_PART, EngineSpec())
    base = timed_fit("streamed/replicated", blobs.points, cfg)
    fit_summary("streamed/replicated", blobs, base)
    with tempfile.TemporaryDirectory(prefix="alid_smoke_") as tmp:
        path = os.path.join(tmp, "points.npy")
        np.save(path, blobs.points)
        spec = cfg.spec._replace(engine="streamed", n_shards=16,
                                 scratch_dir=tmp)
        res = timed_fit("streamed", MemmapSource(path),
                        cfg._replace(spec=spec))
    fit_summary("streamed", blobs, res)
    agreement("streamed vs replicated", res.labels, base.labels)


def assignment_scores(res, rows):
    """(m, C) weighted support affinity of each row to each cluster, from
    the pure-jnp oracle (f32, full-precision contractions), SCORE_ROWS rows
    at a time so the (rows, C * cap) affinity block stays small."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref

    @jax.jit
    def scores(q, sup_v, sup_w, k):
        n_clusters, a, d = sup_v.shape
        aff = ref.affinity_ref(q, sup_v.reshape(-1, d), k)
        return jnp.einsum("mca,ca->mc", aff.reshape(-1, n_clusters, a),
                          sup_w, precision=ref.HIGHEST)

    sup_v, sup_w = jnp.asarray(res.support_v), jnp.asarray(res.support_w)
    k = jnp.float32(res.k)
    return np.concatenate([
        np.asarray(scores(jnp.asarray(rows[i:i + SCORE_ROWS]), sup_v, sup_w,
                          k))
        for i in range(0, len(rows), SCORE_ROWS)])


def phase_serving(blobs, res) -> None:
    from repro.serve import ClusterServer
    rng = np.random.default_rng(SEED)
    rows = blobs.points[np.sort(rng.choice(len(blobs.points), N_QUERIES,
                                           replace=False))]
    with ClusterServer(batch_slots=64, queue_limit=4 * N_QUERIES,
                       policy="block") as server:
        server.add_tenant("sift", res)
        futures = [server.submit(q, tenant="sift") for q in rows]
        served, errors = [], []
        for f in futures:
            try:
                served.append(int(f.result(timeout=600)))
            except Exception as e:                  # noqa: BLE001 - counted
                errors.append(f"{type(e).__name__}: {e}")
                served.append(-2)
        stats = server.stats.snapshot()
    check(not errors, f"serving: {N_QUERIES - len(errors)}/{N_QUERIES} "
          f"futures resolved to a label" + (f" ({errors[0]})" if errors
                                            else ""))
    served = np.asarray(served)
    want = res.predict(rows, backend="ref", batch_size=SCORE_ROWS)
    top2 = np.sort(assignment_scores(res, rows), axis=1)[:, -2:]
    tie = (top2[:, 1] - top2[:, 0]) <= TIE_RTOL * np.abs(top2[:, 1])
    bad = (served != want) & ~tie
    log(f"serving: batches={stats.get('batches')} labeled="
        f"{int((served >= 0).sum())} near_ties={int(tie.sum())} "
        f"mismatches={int(bad.sum())}")
    check(not bad.any(), f"serving: labels equal predict(backend='ref') on "
          f"{N_QUERIES - int(tie.sum())} non-tied rows")


def seed_placement(engine, cfg, n: int) -> dict:
    """Run one mesh round directly; {device: seed rows it computed}."""
    import jax.numpy as jnp
    seeds = jnp.arange(cfg.seeds_per_round, dtype=jnp.int32)
    _, _, results = engine.run_round(jnp.ones((n,), bool), seeds,
                                     jnp.ones_like(seeds, bool))
    return {str(s.device): int(s.data.shape[0])
            for s in results.density.addressable_shards}


def run_mesh(chips: int) -> None:
    import jax

    from repro.core.alid import EngineSpec
    from repro.core.engine import make_engine
    from repro.distributed.context import MeshContext, make_mesh
    check(len(jax.devices()) >= chips,
          f"{len(jax.devices())} devices >= {chips}")
    with phase("fit_1device"):
        blobs, cfg = deployment(N_PART, EngineSpec())
        base = timed_fit("fit_1device", blobs.points, cfg)
        fit_summary("fit_1device", blobs, base)
    mesh = make_mesh((chips,), ("data",), devices=jax.devices()[:chips])
    ctx = MeshContext(mesh=mesh, data_axes=("data",), model_axis="data")
    for n_shards in (0, chips):
        name = f"mesh_{chips}x_shards{n_shards}"
        with phase(name):
            spec = EngineSpec(engine="mesh", n_shards=n_shards, mesh_ctx=ctx)
            engine = make_engine(spec)
            try:
                res = timed_fit(name, blobs.points, cfg._replace(spec=spec),
                                engine=engine)
                placed = seed_placement(engine, cfg, len(blobs.points))
            finally:
                engine.close()
            fit_summary(name, blobs, res)
            log(f"{name}: seeds per device {placed}")
            share = cfg.seeds_per_round // chips
            check(len(placed) == chips and set(placed.values()) == {share},
                  f"{name}: {share} seeds on each of {chips} devices")
            agreement(f"{name} vs 1 device", res.labels, base.labels)


def run_one_chip() -> None:
    with phase("device"):
        phase_device()
    with phase("fit"):
        blobs, res = phase_fit()
    with phase("serving"):
        phase_serving(blobs, res)
    del blobs, res
    with phase("reference"):
        phase_reference()
    with phase("streamed"):
        phase_streamed()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phases 1-5 on one chip; 4: only the PALID "
                         "mesh fits over four chips and the one-device fit "
                         "they are compared with")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    import jax
    try:
        if args.chips == 1:
            run_one_chip()
        else:
            with phase("device"):
                phase_device()
            run_mesh(args.chips)
    except SmokeFailure:
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
