"""ShardedStore — the out-of-core data layout behind sharded CIVS.

ALID's space bound is O(a*(a*+delta)): only the LOCAL affinity graph is ever
materialized. The replicated PALID port honored that for affinity but still
parked the full dataset + LSH tables in every device's HBM. This module
partitions both into S fixed-size shards so the CIVS hot path touches one
shard at a time:

  * points are ordered by projection onto a random direction (the first LSH
    projection vector), then cut into contiguous equal shards — spatially
    coherent, so each shard has a tight bounding ball;
  * each shard carries its own sorted-key LSH tables (projections shared, see
    `build_lsh_sharded`) plus routing metadata (centroid + bounding radius):
    a CIVS query visits a shard only when its ROI ball can intersect the
    shard ball, which is exact — any candidate inside the ROI lives in a
    touched shard by the triangle inequality;
  * the store is a flat pytree whose per-shard leaves all lead with the S
    axis, so a mesh places each device's HBM slice with
    `NamedSharding(P("data"))` (repro.distributed.shardings.store_specs) and
    the fori_loop in sharded CIVS pulls one (cap, d) shard slice per step.

Global <-> local index maps (`shard_of`/`slot_of`, `global_idx`) are O(n)
int32 metadata — the O(n*d) float payload and the affinity blocks are what
the sharding keeps out of the working set (DESIGN.md has the full model).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pipeline import ScratchShards
from repro.core.source import DataSource, iter_source_chunks
from repro.kernels import ops
from repro.lsh.pstable import (LSHParams, ShardedLSHTables, build_lsh_sharded,
                               hash_chunk, make_projections)


class ShardedStore(NamedTuple):
    shards: jax.Array      # (S, cap, d) f32 — padded shard points
    valid: jax.Array       # (S, cap) bool — False on padding
    global_idx: jax.Array  # (S, cap) int32 — original data index, -1 on padding
    shard_of: jax.Array    # (n,) int32 — inverse map: point -> shard
    slot_of: jax.Array     # (n,) int32 — inverse map: point -> slot in shard
    centers: jax.Array     # (S, d) shard centroid (over valid members)
    radii: jax.Array       # (S,) bounding radius around the centroid
    tables: ShardedLSHTables

    @property
    def n_shards(self) -> int:
        return self.shards.shape[0]

    @property
    def shard_cap(self) -> int:
        return self.shards.shape[1]

    @property
    def n_points(self) -> int:
        return self.shard_of.shape[0]


def take(store: ShardedStore, idx: jax.Array) -> jax.Array:
    """Gather point rows by GLOBAL index (the out-of-core points[idx])."""
    safe = jnp.clip(idx, 0, store.n_points - 1)
    return store.shards[store.shard_of[safe], store.slot_of[safe]]


@functools.partial(jax.jit, static_argnames=("params", "n_shards", "backend"))
def _build_store_impl(points: jax.Array, params: LSHParams, rng: jax.Array,
                      n_shards: int, backend: str = "auto") -> ShardedStore:
    n, d = points.shape
    cap = -(-n // n_shards)                    # ceil — last shard padded
    pad = n_shards * cap - n

    # Spatial ordering: project onto the first LSH direction. jax PRNG keys
    # are pure, so regenerating proj here matches build_lsh_sharded exactly
    # without threading the array through.
    proj, _ = make_projections(rng, params, d, jnp.float32)
    # f32 on every backend; bf16 @ f32 promotes to f32, like hash_chunk
    with jax.default_matmul_precision("highest"):
        score = points @ proj[0, 0]
    order = jnp.argsort(score).astype(jnp.int32)           # (n,)

    gidx = jnp.concatenate([order, jnp.full((pad,), -1, jnp.int32)])
    gidx = gidx.reshape(n_shards, cap)
    valid = gidx >= 0
    shards = points[jnp.clip(gidx, 0, n - 1)] * valid[..., None]

    slot = jnp.arange(cap, dtype=jnp.int32)
    sid = jnp.arange(n_shards, dtype=jnp.int32)
    safe_g = jnp.where(valid, gidx, n)
    shard_of = jnp.zeros((n + 1,), jnp.int32).at[safe_g.reshape(-1)].set(
        jnp.broadcast_to(sid[:, None], gidx.shape).reshape(-1))[:n]
    slot_of = jnp.zeros((n + 1,), jnp.int32).at[safe_g.reshape(-1)].set(
        jnp.broadcast_to(slot[None, :], gidx.shape).reshape(-1))[:n]

    cnt = jnp.maximum(jnp.sum(valid, axis=1), 1)
    # centers in f32 even for bf16 shards: a bf16 row-sum accumulator loses
    # mantissa long before shard_cap rows. Routing stays exact — radii are
    # the f32 max distance from this center to the STORED (rounded) points.
    centers = (jnp.sum(shards.astype(jnp.float32), axis=1)
               / cnt[:, None].astype(jnp.float32))
    dist = jax.vmap(
        lambda sh, cen: ops.pairwise_distance(sh, cen[None, :])[:, 0])(
            shards, centers)
    radii = jnp.max(jnp.where(valid, dist, 0.0), axis=1)

    tables = build_lsh_sharded(shards, valid, params, rng, backend)
    return ShardedStore(shards=shards, valid=valid, global_idx=gidx,
                        shard_of=shard_of, slot_of=slot_of,
                        centers=centers, radii=radii, tables=tables)


def build_store(points: jax.Array, params: LSHParams, rng: jax.Array,
                n_shards: int = 8, backend: str = "auto",
                dtype: str = "float32") -> ShardedStore:
    """Partition `points` + LSH into `n_shards` routing-aware shards.

    Consumes `rng` exactly like `build_lsh` (one split -> proj, bias), so a
    store built with the same key is query-for-query consistent with the
    monolithic tables — the basis of the replicated/sharded parity tests.
    `backend` selects the hashing kernel (repro.kernels.ops.lsh_hash).
    `dtype` is the point STORAGE dtype (`repro.kernels.ops.DTYPES`): points
    are rounded to it here, BEFORE hashing, so LSH keys match a replicated
    build over the same rounded points bit-for-bit.
    """
    points = jnp.asarray(points, ops.storage_dtype(dtype))
    n_shards = max(1, min(int(n_shards), points.shape[0]))
    return _build_store_impl(points, params, rng, n_shards, backend)


# ----------------------------------------------------- host-streamed store --
_PAD_KEY_NP = np.uint32(0xFFFFFFFF)
_DEFAULT_CHUNK = 32768


def _round_to_storage(rows: np.ndarray, dtype: str) -> np.ndarray:
    """Round an np.float32 slab to the storage dtype, kept in np.float32.

    numpy has no bf16, so streamed slabs stay np.float32 on the host but
    hold bf16-ROUNDED values: f32 -> bf16 -> f32 is an exact round-trip, so
    a device-side `astype(bfloat16)` of the slab recovers the stored bf16
    bits, and every engine sees the same rounded points."""
    if dtype == "bfloat16":
        return np.asarray(
            jnp.asarray(rows).astype(jnp.bfloat16).astype(jnp.float32))
    return rows


class StreamedStore(NamedTuple):
    """Host-resident analogue of ShardedStore for the streamed engine.

    The O(n·d) payload never leaves the source: shard point rows are fetched
    on demand (`shard_points`) and `device_put` one shard at a time by the
    host CIVS loop. What the store keeps resident is metadata only — the
    spatial order, per-shard sorted-key LSH tables ((S, L, cap) uint32, the
    same scale as the O(n) int32 maps DESIGN.md already budgets), bounding
    balls for routing, and the global table-0 bucket sizes for seeding. The
    tiny (L, m, d) projections live on device so query hashing matches the
    other engines bit-for-bit.
    """
    source: DataSource
    order: np.ndarray        # (n,) int32 — spatial (LSH-projection) order
    global_idx: np.ndarray   # (S, cap) int32 — shard slot -> original index
    valid: np.ndarray        # (S, cap) bool
    sorted_keys: np.ndarray  # (S, L, cap) uint32, ascending per (shard, table)
    perm: np.ndarray         # (S, L, cap) int32 sorted pos -> local slot, -1 pad
    centers: np.ndarray      # (S, d) f64 shard centroids
    radii: np.ndarray        # (S,) f64 bounding radii
    bucket_sizes: np.ndarray  # (n,) int32 global table-0 bucket sizes
    proj: jax.Array          # (L, m, d) — device, shared with query hashing
    bias: jax.Array          # (L, m)
    # scratch persistence of the reordered payloads (core.pipeline): written
    # once at build, turns steady-state shard reads into sequential slab
    # reads; None = re-gather from the source on every fetch (PR 3 behavior)
    scratch: Optional[ScratchShards] = None
    # (S,) int64 per-shard mutation counters, bumped by update_shard_points:
    # ShardBundleCache entries remember the generation they were filled at
    # and a mismatch on probe drops the stale bundle (online deltas would
    # otherwise serve pre-mutation bytes out of the LRU forever)
    generations: Optional[np.ndarray] = None
    # point STORAGE dtype knob (repro.kernels.ops.DTYPES). Slabs are always
    # np.float32 on the host, but with dtype="bfloat16" they hold
    # bf16-rounded values (see _round_to_storage) so the streamed engine's
    # device-side astype(bfloat16) is exact and matches the other engines.
    dtype: str = "float32"

    @property
    def n_shards(self) -> int:
        return self.global_idx.shape[0]

    @property
    def shard_cap(self) -> int:
        return self.global_idx.shape[1]

    @property
    def n_points(self) -> int:
        return self.order.shape[0]

    @property
    def dim(self) -> int:
        return self.source.dim

    def shard_count(self, s: int) -> int:
        return int(self.valid[s].sum())

    def shard_points(self, s: int) -> np.ndarray:
        """Fetch one shard's point rows, zero-padded to (shard_cap, d).

        With scratch persistence this is ONE sequential slab read of the
        reordered payload; without it, rows re-gather from the source (a
        scattered fancy-index read for memmap sources — the spatial order is
        a near-random permutation of file order). Either way the bytes are
        identical, so downstream retrieval cannot tell the tiers apart.
        Peak host memory O(shard)."""
        if self.scratch is not None:
            return self.scratch.read(s)
        return self.gather_shard_points(s)

    def gather_shard_points(self, s: int) -> np.ndarray:
        """Re-gather one shard's rows from the SOURCE, bypassing scratch —
        the bottom of the pipeline's tier chain (cache -> scratch -> here).
        Only valid as a fallback at generation 0: after an in-place
        mutation (`update_shard_points`) the scratch slab is the sole owner
        of the shard's bytes and the source holds the pre-mutation rows —
        `ShardPipeline._read_points` enforces that."""
        m = self.shard_count(s)
        out = np.zeros((self.shard_cap, self.dim), np.float32)
        out[:m] = _round_to_storage(
            np.asarray(self.source.sample(self.global_idx[s, :m]),
                       np.float32), self.dtype)
        return out


def build_store_streamed(source: DataSource, params: LSHParams,
                         rng: jax.Array, n_shards: int = 8,
                         chunk_size: int = 0,
                         scratch_dir: Optional[str] = None,
                         backend: str = "auto",
                         dtype: str = "float32") -> StreamedStore:
    """Build the streamed store shard-by-shard from source chunks.

    Two passes, neither materializing more than O(chunk) rows on device or
    host (beyond the int32/uint32 metadata):

      1. chunked hashing: each chunk is hashed ONCE on DEVICE through
         `pstable.hash_chunk` — the einsum rounds per element, so chunked
         keys/scores are bit-identical to a monolithic `build_lsh` pass —
         keys land in a host (L, n) uint32 table (metadata scale, the same
         O(L·n) as the per-shard sorted tables below) and the host argsorts
         the (n,) score array into the shard order;
      2. per shard: gather its ≤cap rows from the source (for the bounding
         ball only — keys are re-gathered from the pass-1 table, no
         rehash), stable-sort the per-table keys into shard-local sorted
         tables, and take the bounding ball (f64 centroid + exact max
         radius, so the routing test stays conservative).

    `scratch_dir` (non-None) additionally persists each shard's reordered
    rows — already in hand for the bounding ball — to a scratch memmap
    (`core.pipeline.ScratchShards`, "" = system temp dir): the one
    spatially-scattered source gather the build pays anyway buys sequential
    slab reads for every later `shard_points` call. The scratch bytes are
    exactly the re-gather bytes, so persistence cannot change retrieval.

    Consumes `rng` exactly like `build_lsh`/`build_store` (one
    `make_projections`), preserving the engine-parity PRNG schedule; the
    global table-0 bucket sizes are re-aggregated host-side from the
    per-shard tables, so seeding statistics match the replicated engine
    integer-for-integer.

    `dtype` is the point storage dtype: chunks are rounded to it BEFORE
    hashing (matching `build_store`'s pre-hash rounding), and the slabs
    persist the rounded values (see `_round_to_storage`).
    """
    ops.storage_dtype(dtype)  # validate the knob up front
    chunk_size = int(chunk_size) or _DEFAULT_CHUNK
    n, d = source.n, source.dim
    n_shards = max(1, min(int(n_shards), n))
    cap = -(-n // n_shards)
    n_tables = params.n_tables
    proj, bias = make_projections(rng, params, d, jnp.float32)

    scores = np.empty((n,), np.float32)
    keys_full = np.empty((n_tables, n), np.uint32)
    for start, block in iter_source_chunks(source, chunk_size):
        block32 = _round_to_storage(np.asarray(block, np.float32), dtype)
        kk, sc = hash_chunk(jnp.asarray(block32, jnp.float32), proj, bias,
                            params.seg_len, backend)
        stop = start + block.shape[0]
        keys_full[:, start:stop] = np.asarray(kk)
        scores[start:stop] = np.asarray(sc)
    order = np.argsort(scores, kind="stable").astype(np.int32)

    global_idx = np.full((n_shards, cap), -1, np.int32)
    valid = np.zeros((n_shards, cap), bool)
    sorted_keys = np.full((n_shards, n_tables, cap), _PAD_KEY_NP, np.uint32)
    perm = np.full((n_shards, n_tables, cap), -1, np.int32)
    centers = np.zeros((n_shards, d), np.float64)
    radii = np.zeros((n_shards,), np.float64)

    scratch = (ScratchShards.create(n_shards, cap, d, scratch_dir)
               if scratch_dir is not None else None)

    slot = np.arange(cap)
    for s in range(n_shards):
        idx = order[s * cap:min((s + 1) * cap, n)]
        m = idx.shape[0]
        rows = _round_to_storage(np.asarray(source.sample(idx), np.float32),
                                 dtype)
        if scratch is not None:
            scratch.write(s, rows)
        global_idx[s, :m] = idx
        valid[s, :m] = True
        kfull = np.full((n_tables, cap), _PAD_KEY_NP, np.uint32)
        kfull[:, :m] = keys_full[:, idx]
        o = np.argsort(kfull, axis=1, kind="stable").astype(np.int32)
        sorted_keys[s] = np.take_along_axis(kfull, o, axis=1)
        perm[s] = np.where(np.take_along_axis(
            np.broadcast_to((slot < m)[None], (n_tables, cap)), o, axis=1),
            o, -1)
        rows64 = rows.astype(np.float64)
        centers[s] = rows64.mean(axis=0)
        radii[s] = float(np.sqrt(
            ((rows64 - centers[s]) ** 2).sum(-1)).max())

    keys0 = keys_full[0]
    bsizes = np.zeros((n,), np.int64)
    for s in range(n_shards):
        sk0 = sorted_keys[s, 0]
        bsizes += (np.searchsorted(sk0, keys0, side="right")
                   - np.searchsorted(sk0, keys0, side="left"))

    if scratch is not None:
        scratch.flush()
    return StreamedStore(source=source, order=order, global_idx=global_idx,
                         valid=valid, sorted_keys=sorted_keys, perm=perm,
                         centers=centers, radii=radii,
                         bucket_sizes=bsizes.astype(np.int32),
                         proj=proj, bias=bias, scratch=scratch,
                         generations=np.zeros((n_shards,), np.int64),
                         dtype=dtype)


def update_shard_points(store: StreamedStore, s: int,
                        rows: np.ndarray) -> int:
    """Mutate one shard's resident payload in place (online deltas).

    Writes the full (shard_cap, d) zero-padded slab to the scratch memmap —
    the source itself is read-only, so mutation requires scratch persistence
    (`build_store_streamed(..., scratch_dir=...)`) — and bumps the shard's
    generation counter. Any `ShardBundleCache` entry for shard `s` was
    filled at the old generation and gets dropped on its next probe
    (`ShardPipeline.fetch_bundle` passes the current generation), so a
    post-update fetch can never serve pre-update bytes. Returns the new
    generation."""
    if store.scratch is None:
        raise ValueError(
            "update_shard_points needs scratch persistence — build the "
            "store with scratch_dir=... (the DataSource is read-only)")
    if store.generations is None:
        raise ValueError("store predates generation counters — rebuild "
                         "with build_store_streamed")
    rows = _round_to_storage(np.asarray(rows, np.float32), store.dtype)
    if rows.shape != (store.shard_cap, store.dim):
        raise ValueError(f"expected a full ({store.shard_cap}, {store.dim}) "
                         f"zero-padded slab, got {rows.shape}")
    store.scratch.write(s, rows)
    store.generations[s] += 1
    return int(store.generations[s])


@jax.jit
def global_bucket_sizes(store: ShardedStore) -> jax.Array:
    """Per data item: size of its table-0 bucket across ALL shards.

    Projections are shared, so the monolithic bucket of key k is exactly the
    disjoint union of the per-shard buckets of k — summing per-shard counts
    reproduces `bucket_sizes(build_lsh(...))` without ever building the
    monolithic table (used for PALID seeding, paper Sec. 4.6).
    """
    n = store.n_points
    sk0 = store.tables.sorted_keys[:, 0, :]                   # (S, cap)
    perm0 = store.tables.perm[:, 0, :]                        # (S, cap)
    # per-point table-0 key, scattered to global positions
    safe_slot = jnp.clip(perm0, 0, store.shard_cap - 1)
    g_of_sorted = jnp.take_along_axis(store.global_idx, safe_slot, axis=1)
    g_of_sorted = jnp.where(perm0 >= 0, g_of_sorted, n)       # drop pads
    keys = jnp.zeros((n + 1,), sk0.dtype).at[g_of_sorted.reshape(-1)].set(
        sk0.reshape(-1))[:n]
    counts = jax.vmap(
        lambda sk: jnp.searchsorted(sk, keys, side="right")
        - jnp.searchsorted(sk, keys, side="left"))(sk0)       # (S, n)
    return jnp.sum(counts, axis=0).astype(jnp.int32)
