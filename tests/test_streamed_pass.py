"""The host-streamed engine's CIVS pass against the chunk step it replaced.

The streamed engine gathers each routed shard's slots of the pass's global
probe windows into one per-pass buffer and filters and selects once
(`civs.plan_shard_folds`, `gather_shard_slots`, `select_pass`). Folding
`civs.retrieve_chunk` over the same routed shards, lane by lane with the
routing's (lane, shard) select, retrieves the same candidates from the
same windows, so on tie-free data both give the same psi bit for bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.civs import (FOLD_MIN_SLOTS, finalize_retrieval, fold_size,
                             init_retrieval_carry, plan_shard_folds,
                             retrieve_chunk)
from repro.core.store import build_store
from repro.data import auto_lsh_params, make_blobs_with_noise
from repro.kernels.ops import storage_dtype
from repro.lsh.pstable import (LSHParams, hash_queries,
                               shard_bucket_windows_host)

B, A_CAP, DELTA = 4, 16, 12


def _blobs():
    pts = make_blobs_with_noise(n_clusters=4, cluster_size=40, n_noise=120,
                                d=8, seed=11).points
    return pts, auto_lsh_params(pts, probe=8)._replace(n_tables=2)


def _oversized_bucket():
    # one tight cluster of 100 points (one LSH bucket, larger than the
    # probe, spread over several shards) in 40 points of noise
    rng = np.random.default_rng(0)
    pts = np.concatenate([rng.normal(0, 0.05, (100, 8)),
                          rng.uniform(-30, 30, (40, 8))]).astype(np.float32)
    pts = pts[rng.permutation(pts.shape[0])]
    return pts, LSHParams(n_tables=2, n_projections=4, seg_len=4.0, probe=8)


# case: (data, storage dtype, share of (lane, shard) pairs untouched,
#        fold sizes, lanes left with no routed shard, routed shards that no
#        lane touches)
CASES = {
    "oversized_bucket": (_oversized_bucket, "float32", 0.0, "ladder", 0, 0),
    "untouched_pairs": (_blobs, "float32", 0.4, "ladder", 0, 0),
    "empty_lane": (_blobs, "float32", 0.2, "ladder", 1, 0),
    "no_routed_shard": (_blobs, "float32", 1.0, "ladder", 0, 0),
    "bf16": (_blobs, "bfloat16", 0.3, "ladder", 0, 0),
    "exact_and_zero_folds": (_oversized_bucket, "float32", 0.2, "exact", 0,
                             1),
}


@pytest.mark.parametrize("case", list(CASES))
def test_streamed_pass_equals_chunk_folds(case):
    make, dtype, untouched, sizes, empty_lanes, idle_shards = CASES[case]
    points, lshp = make()
    n, d = points.shape
    rng = np.random.default_rng(1)
    store = build_store(jnp.asarray(points), lshp, jax.random.PRNGKey(3),
                        n_shards=4, dtype=dtype)
    n_shards, cap = store.global_idx.shape
    pts_s = store.shards.astype(jnp.float32)     # the streamed f32 slabs

    # B lanes of A_CAP support rows each; the lighter slots hold no support
    sup_idx = np.stack([rng.choice(n, A_CAP, replace=False)
                        for _ in range(B)]).astype(np.int32)
    sup_mask = np.arange(A_CAP)[None, :] < rng.integers(4, A_CAP, (B, 1))
    rows = jnp.asarray(points, storage_dtype(dtype))
    sup_v = rows[jnp.asarray(sup_idx)]
    keys, salts = jax.vmap(lambda q: hash_queries(
        q, store.tables.proj, store.tables.bias, lshp.seg_len))(sup_v)
    # each lane's ROI ball: around its first support row, holding a fifth
    # to a half of the points
    center = sup_v[:, 0].astype(jnp.float32)
    dist = np.linalg.norm(points[None] - np.asarray(center)[:, None], axis=-1)
    radius = jnp.asarray([np.quantile(r, q) for r, q in
                          zip(dist, rng.uniform(0.2, 0.5, B))], jnp.float32)
    active = jnp.asarray(rng.random(n) < 0.9)

    touch = rng.random((B, n_shards)) >= untouched
    touch[:empty_lanes] = False
    touch[:, :idle_shards] = False
    routed = np.flatnonzero(touch.any(axis=0))
    routed = np.union1d(routed, np.arange(idle_shards)).astype(np.int64)

    n_tables = lshp.n_tables
    flat = (n_tables, B * A_CAP)
    st, lo, hi = shard_bucket_windows_host(
        np.asarray(store.tables.sorted_keys)[routed],
        np.asarray(keys).transpose(1, 0, 2).reshape(flat),
        np.asarray(salts).transpose(1, 0, 2).reshape(flat), lshp.probe)
    st, lo, hi = (a.reshape(-1, n_tables, B, A_CAP) for a in (st, lo, hi))

    # the chunk step, shard by shard, each lane keeping its carry on the
    # shards it does not touch
    chunk = jax.jit(jax.vmap(
        lambda c, k, s_, l_, h_, cen, rad, si, sm, t, pts1, sk, pm, gm:
        jax.tree.map(lambda a, b_: jnp.where(t, a, b_), retrieve_chunk(
            c, pts1, sk, pm, gm, k, s_, l_, h_, cen, rad, active, si, sm,
            probe=lshp.probe, p=2.0), c),
        in_axes=(0,) * 10 + (None,) * 4))
    carry = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (B,) + x.shape),
        init_retrieval_carry(DELTA, d, storage_dtype(dtype)))
    for pos, s in enumerate(routed):
        carry = chunk(carry, keys, *(jnp.asarray(a[pos].transpose(1, 0, 2))
                                     for a in (st, lo, hi)), center, radius,
                      jnp.asarray(sup_idx), jnp.asarray(sup_mask),
                      jnp.asarray(touch[:, s]), store.shards[s],
                      store.tables.sorted_keys[s], store.tables.perm[s],
                      store.global_idx[s])
    want = jax.vmap(finalize_retrieval)(carry)

    # the streamed pass
    width = A_CAP * n_tables * lshp.probe
    buf, ids = engine._pass_buffers(B, width, d, dtype)
    plans, counts = plan_shard_folds(st, lo, hi, touch[:, routed].T,
                                     sup_mask, cap, lshp.probe)
    assert counts.sum() <= B * width
    for pos, s in enumerate(routed):
        if sizes == "exact" and counts[pos]:
            size = int(counts[pos])           # the fold's last slot is used
        else:
            size = fold_size(counts[pos], B * width)
        buf, ids = engine._gather_fold(
            buf, ids, pts_s[s], store.tables.perm[s], store.global_idx[s],
            jnp.asarray(plans[pos]), size)
    got = engine._select_pass_batch(buf, ids, center, radius, active,
                                    jnp.asarray(sup_idx),
                                    jnp.asarray(sup_mask), DELTA, 2.0)

    for name, w, g in zip(("psi_idx", "psi_valid", "psi_v", "n_candidates"),
                          want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    n_cand = np.asarray(got[3])
    if case == "no_routed_shard":
        assert routed.size == 0 and not n_cand.any()
    else:
        # some lane has more candidates than psi holds, and some window is
        # cut between two shards
        assert n_cand.max() > DELTA
        assert ((hi - lo > 0).sum(axis=0) >= 2).any()
    if empty_lanes:
        assert not n_cand[:empty_lanes].any()
    if idle_shards:
        assert not counts[:idle_shards].any() and counts.any()


@pytest.mark.parametrize("count,want", [
    (0, FOLD_MIN_SLOTS), (1, FOLD_MIN_SLOTS), (FOLD_MIN_SLOTS, FOLD_MIN_SLOTS),
    (FOLD_MIN_SLOTS + 1, 2 * FOLD_MIN_SLOTS), (300_000, 475_136),
    (475_136, 475_136)])
def test_fold_size_ladder(count, want):
    """Fold sizes double from `FOLD_MIN_SLOTS` and stop at the pass's
    width (here the benchmark's 32 x 232 x 4 x 16 slots)."""
    assert fold_size(np.int64(count), 475_136) == want
