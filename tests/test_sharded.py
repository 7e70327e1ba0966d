"""Parity tests for the sharded / out-of-core CIVS engine.

Shards share the monolithic LSH projections and partition the dataset, so
chunked retrieval is a re-chunking of replicated retrieval — not an
approximation. With probe >= the largest bucket (no probe-window truncation)
the two engines are candidate-for-candidate identical, and whole clustering
runs agree label-for-label across serial, PALID, and sharded drivers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.affinity import estimate_k
from repro.core.alid import (ALIDConfig, detect_clusters,
                             detect_clusters_sharded)
from repro.core.civs import civs_update
from repro.core.lid import init_state, lid_solve
from repro.core.palid import detect_clusters_parallel
from repro.core.roi import estimate_roi
from repro.core.store import ShardedStore, build_store, global_bucket_sizes, take
from repro.data import auto_lsh_params, make_blobs_with_noise
from repro.distributed.context import MeshContext, make_mesh
from repro.lsh.pstable import bucket_sizes, build_lsh
from repro.utils import canonical_labels as canonical


@pytest.fixture(scope="module")
def blobs():
    return make_blobs_with_noise(n_clusters=5, cluster_size=24, n_noise=110,
                                 d=10, seed=3)


@pytest.fixture(scope="module")
def lshp(blobs):
    # probe >= max bucket size -> no probe-window truncation, so the sharded
    # and monolithic retrievals must agree EXACTLY (see module docstring)
    return auto_lsh_params(blobs.points, probe=128)


@pytest.fixture(scope="module")
def store(blobs, lshp):
    return build_store(jnp.asarray(blobs.points), lshp,
                       jax.random.PRNGKey(42), n_shards=5)


def test_store_partitions_dataset(blobs, store):
    n = blobs.points.shape[0]
    gidx = np.asarray(store.global_idx)
    valid = np.asarray(store.valid)
    members = np.sort(gidx[valid])
    assert np.array_equal(members, np.arange(n)), "not an exact partition"
    # inverse maps round-trip and padding is consistent
    assert np.array_equal(gidx[np.asarray(store.shard_of),
                               np.asarray(store.slot_of)], np.arange(n))
    assert (gidx[~valid] == -1).all()
    # take() is the out-of-core points[idx]
    idx = np.arange(0, n, 7)
    np.testing.assert_array_equal(np.asarray(take(store, jnp.asarray(idx))),
                                  blobs.points[idx])


def test_store_bounding_balls_cover_members(blobs, store):
    gidx = np.asarray(store.global_idx)
    valid = np.asarray(store.valid)
    centers = np.asarray(store.centers)
    radii = np.asarray(store.radii)
    for s in range(store.n_shards):
        pts = blobs.points[gidx[s][valid[s]]]
        dist = np.linalg.norm(pts - centers[s], axis=1)
        assert (dist <= radii[s] + 1e-5).all(), s


def test_global_bucket_sizes_match_monolithic(blobs, lshp, store):
    tables = build_lsh(jnp.asarray(blobs.points), lshp, jax.random.PRNGKey(42))
    np.testing.assert_array_equal(np.asarray(bucket_sizes(tables)),
                                  np.asarray(global_bucket_sizes(store)))


def test_chunked_retrieval_matches_monolithic(blobs, lshp, store):
    """The streaming per-shard top-delta merge returns the same candidate set
    as one monolithic query_batch + filter + top_k (satellite acceptance)."""
    pts = jnp.asarray(blobs.points)
    k = estimate_k(pts)
    tables = build_lsh(pts, lshp, jax.random.PRNGKey(42))
    cfg = ALIDConfig(a_cap=32, delta=96, lsh=lshp)
    active = jnp.ones(pts.shape[0], bool)

    for cluster, c_outer in [(0, 1), (2, 2), (4, 3)]:
        seed = int(np.where(blobs.labels == cluster)[0][0])
        state = init_state(pts, jnp.int32(seed), cfg.cap)
        state = lid_solve(state, k, max_iters=50)
        roi = estimate_roi(state.v_beta, state.beta_idx, state.beta_mask,
                           state.x, k, jnp.int32(c_outer))
        mono = civs_update(state, roi, pts, active, tables, lshp, k,
                           a_cap=cfg.a_cap, delta=cfg.delta)
        shrd = civs_update(state, roi, store, active, None, lshp, k,
                           a_cap=cfg.a_cap, delta=cfg.delta)
        # delta did not truncate -> both hold the FULL in-ROI candidate set
        assert int(mono.n_candidates) < cfg.delta
        assert int(mono.n_candidates) == int(shrd.n_candidates)
        pm, mm = np.asarray(mono.state.beta_idx), np.asarray(mono.state.beta_mask)
        ps, ms = np.asarray(shrd.state.beta_idx), np.asarray(shrd.state.beta_mask)
        psi_mono = set(pm[cfg.a_cap:][mm[cfg.a_cap:]].tolist())
        psi_shrd = set(ps[cfg.a_cap:][ms[cfg.a_cap:]].tolist())
        assert psi_mono == psi_shrd
        assert bool(mono.infective_found) == bool(shrd.infective_found)


def test_civs_dispatch_is_type_driven(blobs, lshp, store):
    """civs_update keeps ONE signature; the engine is picked by the points
    operand (array = replicated, ShardedStore = out-of-core)."""
    assert isinstance(store, ShardedStore)
    pts = jnp.asarray(blobs.points)
    k = estimate_k(pts)
    cfg = ALIDConfig(a_cap=16, delta=32, lsh=lshp)
    state = init_state(pts, jnp.int32(0), cfg.cap)
    roi = estimate_roi(state.v_beta, state.beta_idx, state.beta_mask, state.x,
                       k, jnp.int32(1))
    out = civs_update(state, roi, store, jnp.ones(pts.shape[0], bool), None,
                      lshp, k, a_cap=cfg.a_cap, delta=cfg.delta)
    assert out.state.x.shape == (cfg.cap,)


def test_serial_parallel_sharded_label_parity(blobs, lshp):
    """The tentpole acceptance: all three drivers produce the same clustering
    (up to relabeling) — same rng consumption, same seeding statistics, and
    exact retrieval parity make them bit-compatible on tie-free data."""
    cfg = ALIDConfig(a_cap=48, delta=48, lsh=lshp, seeds_per_round=16,
                     max_rounds=20)
    rng = jax.random.PRNGKey(0)
    ser = detect_clusters(blobs.points, cfg, rng)
    shd = detect_clusters_sharded(blobs.points, cfg, rng, n_shards=5)
    mesh = make_mesh((jax.device_count(),), ("data",))
    ctx = MeshContext(mesh=mesh, data_axes=("data",), model_axis="data")
    par = detect_clusters_parallel(blobs.points, cfg, rng, ctx)
    psh = detect_clusters_parallel(blobs.points, cfg, rng, ctx,
                                   n_shards=5 * jax.device_count())

    assert len(ser.densities) > 0
    np.testing.assert_array_equal(canonical(ser.labels), canonical(shd.labels))
    np.testing.assert_array_equal(canonical(ser.labels), canonical(par.labels))
    np.testing.assert_array_equal(canonical(ser.labels), canonical(psh.labels))
    np.testing.assert_allclose(np.sort(ser.densities), np.sort(shd.densities),
                               rtol=1e-6)


def test_global_probe_budget_on_oversized_bucket():
    """Satellite acceptance (ROADMAP item): one `probe`-wide budget is split
    across shards, so a bucket LARGER than probe that spans several shards
    yields the replicated engine's sample size — min(bucket, probe) — not
    min(bucket_s, probe) per shard (up to S*probe before this change)."""
    from repro.core.roi import ROI
    from repro.lsh.pstable import LSHParams

    rng = np.random.default_rng(0)
    # one tight cluster of 100 (a single giant LSH bucket) + 40 spread noise
    cluster = rng.normal(0, 0.05, size=(100, 8)).astype(np.float32)
    noise = rng.uniform(-30, 30, size=(40, 8)).astype(np.float32)
    perm = rng.permutation(140)
    pts_np = np.concatenate([cluster, noise])[perm]
    pts = jnp.asarray(pts_np)

    # L=1 so per-table windows are directly comparable across engines
    lshp = LSHParams(n_tables=1, n_projections=4, seg_len=4.0, probe=8)
    key = jax.random.PRNGKey(42)
    tables = build_lsh(pts, lshp, key)
    assert int(np.asarray(bucket_sizes(tables)).max()) >= 100  # oversized
    # 4 shards of cap 35: the spatially-contiguous cluster spans >= 3 shards
    store4 = build_store(pts, lshp, key, n_shards=4)

    k = estimate_k(pts)
    cfg = ALIDConfig(a_cap=16, delta=64, lsh=lshp)
    seed = int(np.where(perm == 0)[0][0])              # a cluster member
    state = init_state(pts, jnp.int32(seed), cfg.cap)
    # ROI ball covering the whole cluster, so nothing retrieved is filtered
    roi = ROI(center=jnp.mean(jnp.asarray(cluster), 0),
              radius=jnp.float32(5.0), r_in=jnp.float32(0.0),
              r_out=jnp.float32(10.0), pi=jnp.float32(0.0))
    active = jnp.ones(pts.shape[0], bool)
    mono = civs_update(state, roi, pts, active, tables, lshp, k,
                       a_cap=cfg.a_cap, delta=cfg.delta)
    shrd = civs_update(state, roi, store4, active, None, lshp, k,
                       a_cap=cfg.a_cap, delta=cfg.delta)
    n_mono, n_shrd = int(mono.n_candidates), int(shrd.n_candidates)
    # the budget holds: never more than `probe` from the one bucket (the old
    # shard-granular windows would retrieve ~S*probe here)
    assert n_shrd <= lshp.probe
    assert n_mono <= lshp.probe
    # and the sample size matches the replicated engine (±1: the engines
    # sample the bucket in different canonical orders, so the query point
    # itself — excluded as a support member — may fall in only one window)
    assert abs(n_shrd - n_mono) <= 1
    assert n_shrd >= lshp.probe - 1                    # budget fully used


def test_sharded_quality_with_default_probe(blobs):
    """With the default (truncating) probe the engines may retrieve different
    candidates, but the sharded engine must still cluster well."""
    lshp = auto_lsh_params(blobs.points)     # probe=16
    cfg = ALIDConfig(a_cap=48, delta=48, lsh=lshp, seeds_per_round=16,
                     max_rounds=20)
    from repro.utils import avg_f1_score
    res = detect_clusters_sharded(blobs.points, cfg, jax.random.PRNGKey(1),
                                  n_shards=4)
    assert avg_f1_score(blobs.labels, res.labels) > 0.6
