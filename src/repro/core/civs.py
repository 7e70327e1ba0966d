"""Candidate Infective Vertex Search — paper Sec. 4.3.

Queries LSH from EVERY support point of x_hat (multiple locality-sensitive
regions jointly cover the ROI, Fig. 4b), filters candidates to the ROI ball,
keeps the <= delta nearest to the center D, and rebuilds the fixed-capacity
LID buffers as  beta' = alpha ∪ psi  with an EXACT refresh of
(A_beta,alpha x_alpha) (Eq. 17).

Fixed-shape realization: the support is compacted into the first `a_cap`
slots (sorted by weight — an overflow beyond a_cap drops the lightest members
and raises `overflow`), psi occupies the trailing `delta` slots. Dedup is
sort-based; membership tests are masked broadcasts. All shapes are static so
the whole step vmaps over a batch of seeds.

Two retrieval engines sit behind the one `civs_update` signature:

  * replicated — `points`/`tables` are the full dataset + monolithic LSH
    (original path). Every query is a support row, i.e. a data row, so its
    buckets come from the tables' per-point directory (`pstable.probe_rows`)
    instead of hashing and searching;
  * sharded / out-of-core — `points` is a `repro.core.store.ShardedStore`
    (`tables=None`): a fori_loop walks the shards whose bounding ball can
    intersect the ROI ball, probes the shard-local tables, and folds each
    chunk into a running top-delta candidate buffer (`jax.lax.top_k` over
    [buffer ++ chunk]). The per-chunk math is the module-level
    `retrieve_chunk` with an explicit carry (`init_retrieval_carry` /
    `finalize_retrieval`). Because shards partition the dataset and share
    the LSH projections, the union over shards of the chunked retrieval
    equals the monolithic retrieval exactly when probe covers the buckets
    (tested in tests/test_sharded.py), and a GLOBAL probe budget
    (`pstable.shard_bucket_windows`) keeps the per-bucket sample size at
    min(bucket, probe) — the replicated engine's — even when an oversized
    bucket spans many shards. Peak live affinity/candidate state is
    O(shard + a_cap + delta), not O(n).

The host-streamed engine (`engine.StreamedEngine`) does not run
`retrieve_chunk` shard by shard. It plans each pass on the host
(`plan_shard_folds`), folds each routed shard's slots of the global probe
windows into one per-pass candidate buffer (`gather_shard_slots`, one
device_put shard at a time), and runs the filter and selection once per
pass (`select_pass`). That tail is `select_candidates`, the same function
`retrieve_chunk` ends in, so both engines filter, de-duplicate and select
with one implementation (tests/test_streamed_pass.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lid import LIDState
from repro.kernels import ops
from repro.core.roi import ROI
from repro.core.store import ShardedStore
from repro.lsh.pstable import (LSHParams, LSHTables, hash_queries,
                               probe_rows, probe_tables_window, query_salts,
                               shard_bucket_windows)


class CIVSResult(NamedTuple):
    state: LIDState
    infective_found: jax.Array  # () bool — some psi vertex has pi(s_j,x) > pi(x)
    n_candidates: jax.Array     # () int32 — post-filter candidate count (diagnostics)
    overflow: jax.Array         # () bool — support exceeded a_cap


def compact_support(state: LIDState, a_cap: int, support_eps: float):
    """Step 1: compact the support into the first a_cap slots (weight desc)."""
    w = jnp.where(state.beta_mask, state.x, 0.0)
    is_sup = w > support_eps
    n_sup_total = jnp.sum(is_sup)
    order = jnp.argsort(-w)[:a_cap]                       # heaviest first
    sup_idx = state.beta_idx[order]
    sup_v = state.v_beta[order]
    sup_x = w[order]
    n_sup = jnp.minimum(n_sup_total, a_cap)
    slot = jnp.arange(a_cap)
    sup_slot_mask = (slot < n_sup) & (sup_x > support_eps)
    sup_x = jnp.where(sup_slot_mask, sup_x, 0.0)
    sup_x = sup_x / jnp.maximum(jnp.sum(sup_x), 1e-12)    # renorm (overflow drop)
    overflow = n_sup_total > a_cap
    return sup_idx, sup_v, sup_x, sup_slot_mask, overflow


def rebuild_support(state: LIDState, sup_idx, sup_v, sup_x, sup_slot_mask,
                    psi_idx, psi_valid, psi_v, k, a_cap: int, tol: float,
                    p: float, n_candidates, overflow,
                    backend: str = "auto") -> CIVSResult:
    """Step 5: beta' = alpha ∪ psi with exact Ax refresh (Eq. 17) — ONE
    fused masked affinity x weights matvec (`ops.affinity_matvec`): the
    support-slot mask is already folded into `sup_x` (compact_support zeroes
    dropped slots, exactly), the beta-side mask is a row select, so the
    (cap, a_cap) affinity block stays in VMEM on the kernel path."""
    delta = psi_idx.shape[0]
    beta_idx = jnp.concatenate([sup_idx, psi_idx]).astype(jnp.int32)
    beta_mask = jnp.concatenate([sup_slot_mask, psi_valid])
    v_beta = jnp.concatenate([sup_v, psi_v], axis=0)
    x = jnp.concatenate([sup_x, jnp.zeros((delta,), sup_x.dtype)])

    ax = ops.affinity_matvec(v_beta, beta_idx, sup_v, sup_idx, sup_x, k, p,
                             backend=backend)
    ax = jnp.where(beta_mask, ax, 0.0)

    pi = jnp.sum(x * ax)
    infective = jnp.any(psi_valid & (ax[a_cap:] - pi > tol))

    new_state = LIDState(
        beta_idx=beta_idx, beta_mask=beta_mask, v_beta=v_beta, x=x, ax=ax,
        n_iters=state.n_iters, converged=jnp.array(False),
    )
    return CIVSResult(state=new_state, infective_found=infective,
                      n_candidates=n_candidates, overflow=overflow)


def _retrieve_replicated(roi: ROI, points, active, tables, lsh_params,
                         sup_idx, sup_v, sup_slot_mask, delta: int, p: float,
                         backend: str = "auto"):
    """Steps 2-4 against the full dataset + monolithic LSH tables.

    The support rows are data rows (`sup_v` = `points[sup_idx]`), so their
    buckets are read from the directory by `sup_idx`; only the salts are
    computed from `sup_v`, exactly as `query_batch` computes them."""
    n = points.shape[0]
    salts = query_salts(sup_v, tables.proj, tables.bias)
    cands = probe_rows(tables, sup_idx, salts, lsh_params.probe)
    #                                                     (a_cap, L*probe)
    cands = jnp.where(sup_slot_mask[:, None], cands, -1)
    flat = cands.reshape(-1)                              # (a_cap * L * probe,)

    safe = jnp.clip(flat, 0, n - 1)
    valid = flat >= 0
    valid &= active[safe]
    # not already a support member
    member = jnp.any((safe[:, None] == sup_idx[None, :]) & sup_slot_mask[None, :], axis=1)
    valid &= ~member

    # sort-based dedup
    sentinel = jnp.int32(n)  # sorts after every real index
    keys = jnp.where(valid, safe, sentinel)
    skeys = jnp.sort(keys)
    uniq = jnp.concatenate([jnp.array([True]), skeys[1:] != skeys[:-1]])
    cvalid = uniq & (skeys < sentinel)
    cidx = jnp.clip(skeys, 0, n - 1)

    # ROI filter + take the delta nearest to D: distance, radius/validity
    # mask, and the -dist scores come out of ONE fused pass
    vc = points[cidx]
    _, cvalid, neg = ops.roi_filter(vc, roi.center, roi.radius, cvalid, p,
                                    backend=backend)
    n_candidates = jnp.sum(cvalid)

    top_vals, top_pos = jax.lax.top_k(neg, delta)
    psi_valid = top_vals > -jnp.inf
    psi_idx = jnp.where(psi_valid, cidx[top_pos], -1)
    psi_v = points[jnp.clip(psi_idx, 0, n - 1)]
    psi_v = jnp.where(psi_valid[:, None], psi_v, 0.0)
    return psi_idx, psi_valid, psi_v, n_candidates


# --------------------------------------------------- the shared chunk step --
def init_retrieval_carry(delta: int, d: int, dtype=jnp.float32):
    """Empty running top-delta candidate state: (best_neg, best_idx, best_v,
    n_candidates). Fold shards in with `retrieve_chunk`; read the result off
    with `finalize_retrieval`."""
    return (jnp.full((delta,), -jnp.inf, jnp.float32),
            jnp.full((delta,), -1, jnp.int32),
            jnp.zeros((delta, d), dtype),
            jnp.int32(0))


def retrieve_chunk(carry, pts_s, sk, pm, gmap, keys, starts, lo, hi,
                   roi_center, roi_radius, active, sup_idx, sup_slot_mask,
                   probe: int, p: float, backend: str = "auto"):
    """CIVS steps 2-4 for ONE shard/chunk, folded into the running top-delta
    carry — the in-jit sharded engine's chunk step (`_retrieve_sharded`'s
    fori_loop slices the store and calls this). Its filter and selection
    are `select_candidates`, which the host-streamed engine runs once per
    pass over the candidates of every routed shard.

    pts_s (cap_s, d) / sk, pm (L, cap_s) / gmap (cap_s,): one shard's points,
    sorted-key tables, and slot->global map. keys/starts/lo/hi (L, a_cap):
    pre-hashed support queries + this shard's slice of the global probe
    windows (`shard_bucket_windows`). Carry as in `init_retrieval_carry`.
    """
    shard_cap = pts_s.shape[0]

    local = probe_tables_window(sk, pm, keys, starts, lo, hi, probe)
    local = jnp.where(sup_slot_mask[:, None], local, -1)
    flat = local.reshape(-1)                              # (a_cap * L * probe,)
    safe_slot = jnp.clip(flat, 0, shard_cap - 1)
    gidx = jnp.where(flat >= 0, gmap[safe_slot], -1)
    vc = pts_s[safe_slot]
    return select_candidates(carry, vc, gidx, roi_center, roi_radius, active,
                             sup_idx, sup_slot_mask, p, backend)


def select_candidates(carry, vc, gidx, roi_center, roi_radius, active,
                      sup_idx, sup_slot_mask, p: float,
                      backend: str = "auto"):
    """CIVS steps 3-4 on gathered candidates, folded into the running
    top-delta carry: drop inactive points and support members, filter to
    the ROI ball, de-duplicate, and merge the nearest into the carry.

    vc (C, d) / gidx (C,): candidate rows and their global ids, -1 = no
    candidate. Shared by `retrieve_chunk` and the streamed pass
    (`select_pass`).
    """
    best_neg, best_idx, best_v, n_cand = carry
    n = active.shape[0]
    delta = best_neg.shape[0]

    safe_g = jnp.clip(gidx, 0, n - 1)
    valid = (gidx >= 0) & active[safe_g]
    member = jnp.any((safe_g[:, None] == sup_idx[None, :])
                     & sup_slot_mask[None, :], axis=1)
    valid &= ~member
    # fused ROI filter: distance to D, the radius+validity mask, and the
    # -dist top-delta scores in one pass (neg is -inf exactly on ~valid)
    _, valid, neg0 = ops.roi_filter(vc, roi_center, roi_radius, valid, p,
                                    backend=backend)

    # within-chunk dedup (a point can surface from several tables); the
    # sort also fixes a deterministic order for exact-tie distances
    sentinel = jnp.int32(n)
    dkeys = jnp.where(valid, safe_g, sentinel)
    order = jnp.argsort(dkeys)
    sg = dkeys[order]
    sv = vc[order]
    uniq = jnp.concatenate([jnp.array([True]), sg[1:] != sg[:-1]])
    cvalid = uniq & (sg < sentinel)
    n_cand = n_cand + jnp.sum(cvalid)

    neg = jnp.where(uniq, neg0[order], -jnp.inf)
    cand_idx = jnp.where(cvalid, sg, -1).astype(jnp.int32)
    # streaming top-delta merge: buffer ++ chunk -> top_k. Candidate
    # ROWS ride along in the carry so psi needs no end-of-loop gather
    # over the (device-sharded) store — the rows are already local here.
    merged_neg = jnp.concatenate([best_neg, neg])
    merged_idx = jnp.concatenate([best_idx, cand_idx])
    merged_v = jnp.concatenate([best_v, sv], axis=0)
    best_neg, pos = jax.lax.top_k(merged_neg, delta)
    return best_neg, merged_idx[pos], merged_v[pos], n_cand


def finalize_retrieval(carry):
    """Read (psi_idx, psi_valid, psi_v, n_candidates) off a finished carry."""
    best_neg, best_idx, best_v, n_candidates = carry
    psi_valid = best_neg > -jnp.inf
    psi_idx = jnp.where(psi_valid, best_idx, -1)
    psi_v = jnp.where(psi_valid[:, None], best_v, 0.0)
    return psi_idx, psi_valid, psi_v, n_candidates


# ------------------------------------------------ the streamed CIVS pass --
# The host-streamed engine runs CIVS steps 2-4 as one pass over the routed
# shards: each shard's fold gathers only the slots of the global probe
# windows that fall in it into a per-pass buffer, and the filter and
# selection (`select_candidates`) run once over that buffer.

# smallest static slot count of a fold; the ladder doubles from here
FOLD_MIN_SLOTS = 1024


def fold_size(count: int, full: int) -> int:
    """Static slot count of a fold that gathers `count` slots: the least
    power of two from `FOLD_MIN_SLOTS` up that holds them, capped at the
    pass's `full` width, so a fit compiles about ten fold shapes."""
    need = 1 << max(int(count) - 1, 0).bit_length()
    return min(full, max(FOLD_MIN_SLOTS, need))


def plan_shard_folds(starts, lo, hi, touch, sup_mask, cap: int, probe: int):
    """Host plan of a streamed pass's folds, one per routed shard.

    starts/lo/hi (T, L, B, Q): the routed shards' cuts of every lane's
    global probe windows (`shard_bucket_windows_host`); touch (T, B): the
    lanes whose ROI ball meets each shard; sup_mask (B, Q): the support
    slots that query. A shard holds `hi - lo` slots of a (table, lane,
    query) window, and the shards cut each window into consecutive pieces
    in routed order, so a slot's place in the window is the widths of the
    earlier shards' pieces plus its place in this one: unique across
    shards, so the folds never write one buffer slot twice.

    Returns (plans (T, 3, R) int32, counts (T,)), R = L·B·Q rows. For each
    row, `plans[t]` holds the slots the fold gathers from it (0 unless the
    lane touches the shard and the query slot holds a support point), the
    flat (L·cap) position in the shard's `perm` of its first slot, and the
    flat (B·M) place of its first slot in the pass buffers, M = Q·L·probe
    laid out as `retrieve_chunk`'s candidates. counts[t] is the fold's
    slot count.
    """
    n_t, n_l, n_b, n_q = lo.shape
    flat = (n_t, n_l * n_b * n_q)
    i32 = np.int32
    width = hi - lo
    # the pieces' places in their windows (a loop over the few shards:
    # numpy's cumsum across the leading axis is several times slower)
    base = np.zeros_like(width)
    for t in range(1, n_t):
        np.add(base[t - 1], width[t - 1], out=base[t])
    cnt = width * (touch[:, None, :, None] & sup_mask[None, None])
    table = np.arange(n_l, dtype=i32)[:, None, None]
    src = table * cap + starts + lo
    # (lane, query, table) order of the candidates within the buffers
    lane_query = np.arange(n_b * n_q, dtype=i32).reshape(n_b, n_q)
    dst = lane_query * (n_l * probe) + table * probe + base
    plans = np.stack([a.reshape(flat) for a in (cnt, src, dst)], axis=1)
    return plans.astype(i32, copy=False), cnt.reshape(flat).sum(axis=1)


def gather_shard_slots(rows, ids, pts_s, pm, gmap, plan, size: int):
    """One routed shard's fold of a streamed CIVS pass: read `perm`, the
    slot->global map and the points at this shard's slots of the pass's
    probe windows, and write the rows and global ids where the slots sit
    in the pass buffers.

    rows (B, M, d) / ids (B, M): the pass buffers (-1 = no candidate);
    pts_s (cap, d) / pm (L, cap) / gmap (cap,): the shard's bundle; plan
    (3, R): the shard's rows of `plan_shard_folds`. `size` is the fold's
    static slot count (`fold_size`), at least the plan's; slots past the
    plan's write nothing. The windows are exact bucket ranges of the
    shard's sorted keys (`searchsorted`), so the keys are not read.
    """
    cnt, src, dst = plan
    n_rows = cnt.shape[0]
    first = jnp.cumsum(cnt) - cnt                 # each row's first slot
    count = first[-1] + cnt[-1]
    slot = jnp.arange(size, dtype=jnp.int32)
    # each slot's row: the non-empty rows' first slots scattered (empty
    # rows out of range, each to its own place), then a running max
    head = jnp.where(cnt > 0, first, size + jnp.arange(n_rows,
                                                       dtype=jnp.int32))
    row = jax.lax.cummax(jnp.zeros((size,), jnp.int32).at[head].set(
        jnp.arange(n_rows, dtype=jnp.int32), mode="drop",
        unique_indices=True))
    at = jnp.stack([src - first, dst - first], axis=-1)[row] + slot[:, None]
    flat_pm = pm.reshape(-1)
    local = flat_pm[jnp.clip(at[:, 0], 0, flat_pm.shape[0] - 1)]
    safe = jnp.clip(local, 0, gmap.shape[0] - 1)
    gid = jnp.where(local >= 0, gmap[safe], -1)
    n_b, m, d = rows.shape
    # slots past the count go out of range, each to its own place, and drop
    to = jnp.where(slot < count, at[:, 1], n_b * m + slot)
    ids = ids.reshape(-1).at[to].set(gid, mode="drop", unique_indices=True)
    rows = rows.reshape(-1, d).at[to].set(pts_s[safe].astype(rows.dtype),
                                          mode="drop", unique_indices=True)
    return rows.reshape(n_b, m, d), ids.reshape(n_b, m)


def select_pass(rows, ids, roi_center, roi_radius, active, sup_idx,
                sup_slot_mask, delta: int, p: float, backend: str = "auto"):
    """One lane's filter and selection over its pass buffer (rows (M, d),
    ids (M,)), from an empty carry: (psi_idx, psi_valid, psi_v,
    n_candidates), as `finalize_retrieval` reads them."""
    carry = init_retrieval_carry(delta, rows.shape[-1], rows.dtype)
    return finalize_retrieval(select_candidates(
        carry, rows, ids, roi_center, roi_radius, active, sup_idx,
        sup_slot_mask, p, backend))


# Conservative slack on the ball-intersection routing test: shard radii and
# the triangle inequality are evaluated in f32, so a candidate exactly on the
# ROI boundary must not be lost to rounding in the shard-level test. Applied
# RELATIVE to the ball scales (f32 rounding is relative): over-admitting a
# shard costs one extra probe, under-admitting breaks exactness.
_ROUTE_EPS = 1e-4


def _retrieve_sharded(roi: ROI, store: ShardedStore, active, lsh_params,
                      sup_idx, sup_v, sup_slot_mask, delta: int, p: float,
                      backend: str = "auto"):
    """Steps 2-4, out-of-core: stream shards through a running top-delta merge.

    Each fori_loop step materializes ONE shard's points + tables (a dynamic
    slice on the leading S axis — the axis a mesh shards over devices) and
    only when the shard's bounding ball intersects the ROI ball. Candidates
    live in a (delta,) running buffer; cross-shard dedup is free because the
    shards partition the dataset. The per-shard math is `retrieve_chunk` —
    the same function the host-streamed engine drives one device_put at a
    time.
    """
    n_shards = store.shards.shape[0]
    keys, salts = hash_queries(sup_v, store.tables.proj, store.tables.bias,
                               lsh_params.seg_len, backend)  # (L, a_cap)
    # Global probe budget (ROADMAP item): one `probe`-wide salted window per
    # (table, query) is split across shards proportionally to their bucket
    # spans, so an oversized bucket yields min(bucket, probe) candidates in
    # total — the replicated engine's sample size — instead of per-shard
    # windows that grow with the shard count.
    win_starts, win_lo, win_hi = shard_bucket_windows(
        store.tables.sorted_keys, keys, salts, lsh_params.probe)

    d = store.shards.shape[2]

    def chunk_step(s, carry):
        sk = jax.lax.dynamic_index_in_dim(store.tables.sorted_keys, s, 0,
                                          keepdims=False)  # (L, cap)
        pm = jax.lax.dynamic_index_in_dim(store.tables.perm, s, 0,
                                          keepdims=False)  # (L, cap)
        gmap = jax.lax.dynamic_index_in_dim(store.global_idx, s, 0,
                                            keepdims=False)  # (cap,)
        pts_s = jax.lax.dynamic_index_in_dim(store.shards, s, 0,
                                             keepdims=False)  # (cap, d)
        st = jax.lax.dynamic_index_in_dim(win_starts, s, 0, keepdims=False)
        lo = jax.lax.dynamic_index_in_dim(win_lo, s, 0, keepdims=False)
        hi = jax.lax.dynamic_index_in_dim(win_hi, s, 0, keepdims=False)
        return retrieve_chunk(carry, pts_s, sk, pm, gmap, keys, st, lo, hi,
                              roi.center, roi.radius, active, sup_idx,
                              sup_slot_mask, probe=lsh_params.probe, p=p,
                              backend=backend)

    def shard_step(s, carry):
        if p != 2.0:
            # shard radii are Euclidean; ball routing is only sound when the
            # ROI metric matches, so other norms probe every shard (exact,
            # just unrouted)
            return chunk_step(s, carry)
        # ROI-ball vs shard-ball routing (exact by the triangle inequality:
        # every point within roi.radius of the center lies in a shard whose
        # ball intersects the ROI ball). lax.cond skips the gather + probe
        # for non-intersecting shards; under vmap (batched seeds in
        # lockstep) it lowers to select, so the saving materializes in the
        # unbatched / host-streamed deployments, not the vmapped drivers.
        c_dist = ops.pairwise_distance(store.centers[s][None, :],
                                       roi.center[None, :], p,
                                       backend=backend)[0, 0]
        reach = roi.radius + store.radii[s]
        touch = c_dist <= reach + _ROUTE_EPS * (1.0 + reach)
        return jax.lax.cond(touch, lambda c: chunk_step(s, c), lambda c: c,
                            carry)

    carry = jax.lax.fori_loop(
        0, n_shards, shard_step,
        init_retrieval_carry(delta, d, store.shards.dtype))
    return finalize_retrieval(carry)


@functools.partial(jax.jit, static_argnames=("a_cap", "delta", "lsh_params",
                                             "tol", "support_eps", "p",
                                             "backend"))
def civs_update(
    state: LIDState,
    roi: ROI,
    points: jax.Array | ShardedStore,
    active: jax.Array,
    tables: LSHTables | None,
    lsh_params: LSHParams,
    k: jax.Array,
    a_cap: int,
    delta: int,
    tol: float = 1e-5,
    support_eps: float = 1e-6,
    p: float = 2.0,
    backend: str = "auto",
) -> CIVSResult:
    cap = a_cap + delta
    assert state.x.shape[0] == cap, (state.x.shape, cap)

    sup_idx, sup_v, sup_x, sup_slot_mask, overflow = compact_support(
        state, a_cap, support_eps)

    if isinstance(points, ShardedStore):
        psi_idx, psi_valid, psi_v, n_candidates = _retrieve_sharded(
            roi, points, active, lsh_params, sup_idx, sup_v, sup_slot_mask,
            delta, p, backend)
    else:
        psi_idx, psi_valid, psi_v, n_candidates = _retrieve_replicated(
            roi, points, active, tables, lsh_params, sup_idx, sup_v,
            sup_slot_mask, delta, p, backend)

    return rebuild_support(state, sup_idx, sup_v, sup_x, sup_slot_mask,
                           psi_idx, psi_valid, psi_v, k, a_cap, tol, p,
                           n_candidates, overflow, backend)
