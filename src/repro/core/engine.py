"""Unified ClusterEngine API — ONE peel-reduce driver over four engines.

This module is the public face of dominant-cluster detection:

    cfg = ALIDConfig(spec=EngineSpec(engine="streamed", n_shards=8), ...)
    clustering = fit(MemmapSource("x.npy"), cfg, rng)   # -> Clustering
    labels = clustering.predict(new_points)     # per-query assignment

`fit` ingests a `repro.core.source.DataSource` (memmap / chunked / in-memory)
or a legacy (n, d) array, auto-wrapped; only the streamed engine never
materializes the source.

`fit` runs the host-level peeling loop of paper Sec. 4.4: rounds of batched
seeds, each resolved by the PALID reducer (Sec. 4.6) — a point belongs to
the claiming instance of maximum density, exact ties broken deterministically
toward the larger seed row id. That reducer exists exactly ONCE
(`resolve_claims`, a jitted segment-max scatter) and every engine routes
through it; the paper's MapReduce split survives as map = `run_round`'s
vmapped/shard_mapped ALID instances, reduce = `resolve_claims`.

Engines implement the small `Engine` protocol and differ only in where the
retrieval substrate lives:

  * ReplicatedEngine — full dataset + monolithic LSH on the local device(s);
  * ShardedEngine    — out-of-core `ShardedStore`, CIVS streams one shard at
                       a time inside jit (DESIGN.md §3);
  * MeshEngine       — the PALID map phase sharded over a device mesh, with
                       either a replicated store or (n_shards > 0) the
                       ShardedStore, one HBM slice per device between
                       rounds and gathered whole for a round;
  * StreamedEngine   — the ALID outer loop lifted to HOST level over a
                       host-resident `StreamedStore`: one routed shard is
                       device_put at a time into a double-buffered slot, so
                       peak device memory is O(shard + cap) for datasets
                       beyond device (or host-aggregate) HBM (DESIGN.md
                       §3.3).

All four consume the PRNG stream identically (one split for the LSH build,
one per round for seeding) and share seeding statistics, so on tie-free data
they produce identical labels (tests/test_engine.py parametrizes the parity
suite over every engine x exhaustive mode).
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional, Protocol

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.alid import (ALIDConfig, Clustering, EngineSpec, SeedResult,
                             _sample_seeds, alid_from_seed, storage_dtype)
from repro.core.affinity import estimate_k
from repro.core.civs import (_ROUTE_EPS, compact_support, finalize_retrieval,
                             init_retrieval_carry, rebuild_support,
                             retrieve_chunk)
from repro.core.lid import init_state_from, lid_solve
from repro.core.pipeline import PipelineStats, ShardPipeline
from repro.core.resilience import DEFAULT_RETRY, RetryPolicy, resilient
from repro.core.roi import estimate_roi
from repro.core.source import (DataSource, as_source, strided_sample_indices)
from repro.core.store import (build_store, build_store_streamed,
                              global_bucket_sizes)
from repro.distributed.context import MeshContext, make_mesh
from repro.distributed.shardings import store_specs
from repro.lsh.pstable import (bucket_sizes, build_lsh, hash_queries,
                               shard_bucket_windows_host)
from repro.utils import trace
from repro.utils.trace import span

__all__ = ["Engine", "EngineSpec", "Clustering", "DataSource", "fit",
           "make_engine", "resolve_claims", "ReplicatedEngine",
           "ShardedEngine", "MeshEngine", "StreamedEngine"]


# ------------------------------------------------------------ the reducer --
@functools.partial(jax.jit, static_argnames=("n",))
def resolve_claims(member_idx: jax.Array, member_mask: jax.Array,
                   dens: jax.Array, seed_valid: jax.Array, n: int):
    """THE claim reducer (paper Sec. 4.6) — the only implementation.

    Segment-max over all (seed row, member) claims: each point goes to the
    claiming instance of maximum density; among exactly-tied densities
    (within 1e-9) the larger seed row id wins, deterministically. Every
    engine resolves its round through this function, so serial, sharded and
    mesh runs agree even on deliberately tied data (tests/test_engine.py).

    member_idx/member_mask: (s, cap); dens/seed_valid: (s,).
    Returns (claimed (n,) bool, best_row (n,) int32, best_dens (n,) f32).
    """
    s_batch, cap = member_idx.shape
    flat_idx = member_idx.reshape(-1)
    flat_valid = member_mask.reshape(-1) & (flat_idx >= 0)
    flat_valid &= jnp.repeat(seed_valid, cap)
    flat_dens = jnp.repeat(dens, cap)
    safe = jnp.clip(flat_idx, 0, n - 1)

    # reduce 1: max density claiming each point
    best_dens = jnp.full((n,), -jnp.inf, jnp.float32).at[safe].max(
        jnp.where(flat_valid, flat_dens, -jnp.inf))
    # reduce 2: among winners, deterministic tie-break on seed row id
    flat_row = jnp.repeat(jnp.arange(s_batch, dtype=jnp.int32), cap)
    is_winner = flat_valid & (flat_dens >= best_dens[safe] - 1e-9)
    best_row = jnp.full((n,), -1, jnp.int32).at[safe].max(
        jnp.where(is_winner, flat_row, -1))

    claimed = best_row >= 0
    return claimed, best_row, best_dens


# ---------------------------------------------------------- map functions --
@functools.partial(jax.jit, static_argnames=("cfg",))
def _map_round(points, active, tables, seeds, k, cfg: ALIDConfig):
    """Local map phase: a vmapped batch of ALID instances. `points` is the
    replicated array (+`tables`) or a ShardedStore (`tables=None`)."""
    return jax.vmap(
        lambda s: alid_from_seed(points, active, tables, s, k, cfg))(seeds)


@functools.partial(jax.jit, static_argnames=("cfg", "ctx"))
def _map_round_mesh(points, active, tables, seeds, k, cfg: ALIDConfig,
                    ctx: MeshContext):
    """PALID map phase: seeds sharded over the data axes; every device runs
    its seed batch under vmap against the whole dataset. `points` is the
    replicated array (+ LSH `tables`) or the mesh-placed ShardedStore
    (`tables=None`), whose per-device slices XLA gathers for the round: the
    Pallas kernels inside cannot be partitioned by GSPMD, so the map phase
    is a shard_map and each device holds the full store while it runs."""
    data = ctx.data_axes if len(ctx.data_axes) > 1 else ctx.data_axes[0]

    def shard_fn(pts, act, tab, seeds_local):
        return jax.vmap(
            lambda s: alid_from_seed(pts, act, tab, s, k, cfg))(seeds_local)

    rep = lambda leaf: P(*([None] * leaf.ndim))
    return jax.shard_map(
        shard_fn, mesh=ctx.mesh,
        in_specs=(jax.tree.map(rep, points), P(None),
                  jax.tree.map(rep, tables), P(data)),
        out_specs=P(data),
        check_vma=False,
    )(points, active, tables, seeds)


# ----------------------------------------------------------------- engines --
class Engine(Protocol):
    """One retrieval/compute substrate behind the shared peel-reduce driver.

    build_source() ingests a DataSource (consuming rng exactly once), after
    which `k` and `bucket_sizes` are available; run_round() maps a batch of
    seeds and resolves their claims through `resolve_claims`. build() is the
    legacy array entry (auto-wrapped as an InMemorySource); device-resident
    engines materialize the source, the streamed engine never does.
    """

    k: jax.Array

    def build(self, points: jax.Array, cfg: ALIDConfig,
              rng: jax.Array) -> None: ...

    def build_source(self, source: DataSource, cfg: ALIDConfig,
                     rng: jax.Array) -> None: ...

    def run_round(self, active: jax.Array, seeds: jax.Array,
                  seed_valid: jax.Array
                  ) -> tuple[jax.Array, jax.Array, SeedResult]: ...

    def prepare_round(self, seeds: jax.Array) -> None: ...

    def close(self) -> None: ...

    @property
    def bucket_sizes(self) -> jax.Array: ...

    @property
    def probe_counter(self) -> str: ...


# rows drawn for k estimation when cfg.k is None (mirrors estimate_k default)
_K_SAMPLE = 512


class _EngineBase:
    def __init__(self) -> None:
        self._bsizes = None
        self._tables = None
        self.k = None
        self._cfg: Optional[ALIDConfig] = None
        self._n = 0

    def _setup_k(self, source: DataSource, cfg: ALIDConfig) -> None:
        self._cfg = cfg
        self._n = source.n
        if cfg.k is not None:
            self.k = jnp.float32(cfg.k)
        else:
            # STRIDED subsample (not a prefix — point order is spatially
            # meaningful, see affinity.estimate_k); drawn through the source
            # interface so k estimation works chunked/out-of-core, and from
            # the SAME indices on every engine (parity contract).
            idx = strided_sample_indices(source.n, _K_SAMPLE)
            self.k = estimate_k(jnp.asarray(source.sample(idx), jnp.float32),
                                backend=cfg.backend)

    def _setup_k_from_points(self, points, cfg: ALIDConfig) -> None:
        """build()-side k setup: a no-op when build_source already drew the
        sample from the ORIGINAL source (avoids bouncing the materialized
        O(n·d) array back to host just to re-gather 512 rows)."""
        if self._cfg is cfg and self.k is not None:
            self._n = points.shape[0]
            return
        self._setup_k(as_source(np.asarray(points)), cfg)

    def build_source(self, source: DataSource, cfg: ALIDConfig,
                     rng: jax.Array) -> None:
        """Default ingestion: sample k from the source, then materialize it
        as one device array (the replicated/sharded/mesh engines are
        device-resident by design; only StreamedEngine overrides this with a
        non-materializing build)."""
        self._setup_k(source, cfg)
        self.build(jnp.asarray(source.as_array(), jnp.float32), cfg, rng)

    @property
    def bucket_sizes(self) -> jax.Array:
        assert self._bsizes is not None, "call build() first"
        return self._bsizes

    @property
    def probe_counter(self) -> str:
        """The trace counter of this engine's CIVS bucket lookups: monolithic
        tables are read through their bucket directory, shard tables are
        binary-searched."""
        return ("lsh.probes_directory" if self._tables is not None
                else "lsh.probes_searched")

    def prepare_round(self, seeds) -> None:
        """Optional round-level overlap hook: the driver announces the seed
        batch it SPECULATES the next round will use while the current round
        still runs. Default: nothing to prepare (device-resident engines
        gather seed rows inside jit)."""

    def close(self) -> None:
        """Release engine-held resources (device slots, caches, scratch
        files, worker threads). The `fit` driver calls this on the way out;
        default engines hold nothing that outlives their arrays."""

    def _reduce(self, results: SeedResult, seed_valid: jax.Array):
        claimed, best_row, _ = resolve_claims(
            results.member_idx, results.member_mask, results.density,
            seed_valid, n=self._n)
        return claimed, best_row, results


class ReplicatedEngine(_EngineBase):
    """Full dataset + monolithic LSH tables in device memory (original path)."""

    def __init__(self, spec: EngineSpec = EngineSpec()):
        super().__init__()
        self.spec = spec

    def build(self, points, cfg, rng):
        self._setup_k_from_points(points, cfg)
        # round to the storage dtype BEFORE hashing (k estimation above
        # samples the unrounded source, identically across engines)
        self._points = jnp.asarray(points, storage_dtype(cfg.dtype))
        self._tables = build_lsh(self._points, cfg.lsh, rng, cfg.backend)
        self._bsizes = bucket_sizes(self._tables)

    def run_round(self, active, seeds, seed_valid):
        results = _map_round(self._points, active, self._tables, seeds,
                             self.k, self._cfg)
        return self._reduce(results, seed_valid)


class ShardedEngine(_EngineBase):
    """Out-of-core ShardedStore: CIVS streams one shard at a time, the live
    working set is O(shard + cap), not O(n) (DESIGN.md §3)."""

    def __init__(self, spec: EngineSpec):
        super().__init__()
        self.spec = spec

    def build(self, points, cfg, rng):
        self._setup_k_from_points(points, cfg)
        self._store = build_store(points, cfg.lsh, rng,
                                  n_shards=max(1, self.spec.n_shards),
                                  backend=cfg.backend, dtype=cfg.dtype)
        self._bsizes = global_bucket_sizes(self._store)

    def run_round(self, active, seeds, seed_valid):
        results = _map_round(self._store, active, None, seeds, self.k,
                             self._cfg)
        return self._reduce(results, seed_valid)


class MeshEngine(_EngineBase):
    """PALID over a device mesh (paper Alg. 3): the map phase shards the
    seed batch over the data axes.

    Memory bound: with replicated data (n_shards = 0) every device holds the
    whole dataset and its LSH tables. n_shards > 0 retrieves through the
    ShardedStore and keeps it one slice per device BETWEEN rounds only: a
    round gathers the whole store onto every device (`_map_round_mesh`), so
    peak device memory is the full store, as with replicated data, and the
    round pays the gather. Holding 1/n_data per device through a round needs
    each shard fetched inside the shard_map, which needs collectives inside
    ALID's data-dependent while loops; devices run those for different trip
    counts, so that fetch does not exist yet.

    Straggler story as in the paper: seeds are over-decomposed, the
    instances on one device run in masked lockstep under vmap, and a round
    ends with its slowest device's batch; a lost device's seed range
    is re-issued by the host driver on the next round (fit is restartable at
    round granularity)."""

    def __init__(self, spec: EngineSpec):
        super().__init__()
        self.spec = spec
        self.ctx = spec.mesh_ctx

    def build(self, points, cfg, rng):
        self._setup_k_from_points(points, cfg)
        if self.ctx is None:
            mesh = make_mesh((jax.device_count(),), ("data",))
            self.ctx = MeshContext(mesh=mesh, data_axes=("data",),
                                   model_axis="data")
        n_data = self.ctx.n_data
        assert cfg.seeds_per_round % n_data == 0, \
            (cfg.seeds_per_round, n_data)
        self._points = jnp.asarray(points, storage_dtype(cfg.dtype))
        n_shards = self.spec.n_shards
        if n_shards > 0:
            assert n_shards % n_data == 0, (n_shards, n_data)
            store = build_store(points, cfg.lsh, rng, n_shards=n_shards,
                                backend=cfg.backend, dtype=cfg.dtype)
            self._store = jax.device_put(store, jax.tree.map(
                lambda s: NamedSharding(self.ctx.mesh, s), store_specs(store),
                is_leaf=lambda s: isinstance(s, P)))
            self._bsizes = global_bucket_sizes(self._store)
            self._tables = None
        else:
            self._store = None
            self._tables = build_lsh(self._points, cfg.lsh, rng, cfg.backend)
            self._bsizes = bucket_sizes(self._tables)

    def run_round(self, active, seeds, seed_valid):
        data = self._store if self._store is not None else self._points
        results = _map_round_mesh(data, active, self._tables, seeds, self.k,
                                  self._cfg, self.ctx)
        return self._reduce(results, seed_valid)


# ------------------------------------------- streamed (host-driven) engine --
# The jitted stages of the host-level ALID loop. Each mirrors one piece of
# `alid_from_seed`'s while-loop body, vmapped over the seed batch; the host
# driver composes them with per-lane select masks — the explicit analogue of
# what vmap-of-while_loop does implicitly — so the math (and therefore the
# labels, on tie-free data) is identical to the in-jit engines.

@functools.partial(jax.jit, static_argnames=("cap", "dtype"))
def _init_states_batch(seed_rows, seeds, cap: int, dtype: str = "float32"):
    # storage rounding is idempotent: slab rows are already bf16-rounded
    # (exact recast) and raw source rows round here — same bits either way
    seed_rows = seed_rows.astype(storage_dtype(dtype))
    return jax.vmap(lambda v, s: init_state_from(v, s, cap))(seed_rows, seeds)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _lid_batch(state, k, cfg: ALIDConfig):
    return jax.vmap(lambda s: lid_solve(s, k, max_iters=cfg.t_lid,
                                        tol=cfg.tol, p=cfg.p,
                                        backend=cfg.backend,
                                        sweep_steps=cfg.sweep_steps,
                                        refresh_every=cfg.refresh_every,
                                        support_eps=cfg.support_eps))(state)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _roi_batch(state, k, c, cfg: ALIDConfig):
    return jax.vmap(
        lambda s, ci: estimate_roi(s.v_beta, s.beta_idx, s.beta_mask, s.x,
                                   k, ci, r0=cfg.r0, p=cfg.p,
                                   support_eps=cfg.support_eps,
                                   backend=cfg.backend))(state, c)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _civs_begin_batch(state, cfg: ALIDConfig):
    return jax.vmap(
        lambda s: compact_support(s, cfg.a_cap, cfg.support_eps))(state)


@functools.partial(jax.jit, static_argnames=("seg_len", "backend"))
def _hash_queries_batch(sup_v, proj, bias, seg_len: float,
                        backend: str = "auto"):
    return jax.vmap(
        lambda q: hash_queries(q, proj, bias, seg_len, backend))(sup_v)


@functools.partial(jax.jit, static_argnames=("b", "delta", "d", "dtype"))
def _init_carry_batch(b: int, delta: int, d: int, dtype: str = "float32"):
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (b,) + x.shape),
                        init_retrieval_carry(delta, d, storage_dtype(dtype)))


@functools.partial(jax.jit, static_argnames=("probe", "p", "backend",
                                             "dtype"))
def _stream_chunk_batch(carry, pts_s, sk, pm, gmap, keys, starts, lo, hi,
                        center, radius, active, sup_idx, sup_slot_mask,
                        touch, probe: int, p: float, backend: str = "auto",
                        dtype: str = "float32"):
    """One device-resident shard folded into every seed lane's carry.

    The shard leaves (pts_s/sk/pm/gmap) broadcast; everything per-seed maps.
    `touch` replays the lax.cond-under-vmap select of `_retrieve_sharded`:
    lanes whose ROI ball misses the shard ball keep their carry untouched.
    The np.float32 slab holds storage-rounded values, so the astype to the
    storage dtype is exact (matching ShardedEngine's `store.shards` dtype).
    """
    pts_s = pts_s.astype(storage_dtype(dtype))

    def one(carry1, keys1, st1, lo1, hi1, cen1, rad1, sidx1, smask1, t1):
        new = retrieve_chunk(carry1, pts_s, sk, pm, gmap, keys1, st1, lo1,
                             hi1, cen1, rad1, active, sidx1, smask1,
                             probe=probe, p=p, backend=backend)
        return jax.tree.map(lambda a, b_: jnp.where(t1, a, b_), new, carry1)

    return jax.vmap(one)(carry, keys, starts, lo, hi, center, radius,
                         sup_idx, sup_slot_mask, touch)


@jax.jit
def _finalize_batch(carry):
    return jax.vmap(finalize_retrieval)(carry)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _civs_finish_batch(state, sup_idx, sup_v, sup_x, sup_mask, psi_idx,
                       psi_valid, psi_v, k, n_cand, overflow,
                       cfg: ALIDConfig):
    return jax.vmap(
        lambda st, si, sv, sx, sm, pidx, pval, pv, nc, ov: rebuild_support(
            st, si, sv, sx, sm, pidx, pval, pv, k, cfg.a_cap, cfg.tol,
            cfg.p, nc, ov, cfg.backend))(
        state, sup_idx, sup_v, sup_x, sup_mask, psi_idx, psi_valid, psi_v,
        n_cand, overflow)


@jax.jit
def _select_lanes(lane, new_tree, old_tree):
    """Per-lane select over batched pytrees (lane (B,) bool broadcasts over
    each leaf's trailing dims) — the host analogue of vmapped-while masking."""
    def sel(a, b):
        shape = (lane.shape[0],) + (1,) * (a.ndim - 1)
        return jnp.where(lane.reshape(shape), a, b)
    return jax.tree.map(sel, new_tree, old_tree)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _seed_results_batch(state, c, overflow, cfg: ALIDConfig):
    sup = state.beta_mask & (state.x > cfg.support_eps)
    return SeedResult(
        member_idx=jnp.where(sup, state.beta_idx, -1),
        member_w=jnp.where(sup, state.x, 0.0),
        member_mask=sup,
        density=jnp.sum(state.x * state.ax, axis=-1),
        n_outer=c - 1,
        overflow=overflow,
    )


class StreamedEngine(_EngineBase):
    """Host-streamed out-of-core engine: the dataset stays behind a
    DataSource, the store (`core.store.StreamedStore`) is built shard-by-
    shard from source chunks, and the ALID outer loop runs at HOST level.
    Shard I/O goes through `core.pipeline.ShardPipeline`: payloads persist
    once to a scratch memmap at build, hot bundles sit in a bounded host
    LRU, and (prefetch_depth >= 1) a background reader walks each CIVS
    pass's ROUTED shard list ahead of the compute loop, device_put-ing
    bundles into a depth-k slot ring so disk read + H2D upload of shard s+1
    overlap the device compute of shard s. Peak device memory is
    O((prefetch_depth+1)·shard + cap); peak host memory adds the LRU budget
    (DESIGN.md §3.3).

    The PRNG schedule (one split for the store build, one per round for
    seeding), the seeding statistics (exact global bucket sizes), the chunk
    math (`civs.retrieve_chunk` — shared with ShardedEngine), and the claim
    reducer are all identical to the other engines — and the pipeline
    consumes shards in routed order regardless of arrival — so on tie-free
    data the streamed engine produces the same labels as the replicated one
    (pipelined or not) and stays in the parity suite."""

    def __init__(self, spec: EngineSpec):
        super().__init__()
        self.spec = spec
        self.stats = PipelineStats()
        self._pipeline: Optional[ShardPipeline] = None
        self._store = None
        self._executor = None               # round-overlap seed prefetch
        # fault-injection hooks (core.resilience.PipelineFaults): set BEFORE
        # build_source/fit and they are installed on the shard pipeline —
        # None in production, used by chaos tests / run_palid --inject-faults
        self.faults = None
        # checksum verification on scratch/cache reads; benchmarks/
        # resilience.py turns it off to measure the clean-path overhead
        self.verify_checksums = True
        # pending (seeds_np, Future[device rows]) pairs, newest last. Two
        # can be in flight at once: round r's rows (ready to consume) and
        # round r+1's speculation (announced before round r runs)
        self._prepared: list = []

    def build_source(self, source, cfg, rng):
        self._setup_k(source, cfg)
        self._store = build_store_streamed(
            source, cfg.lsh, rng, n_shards=max(1, self.spec.n_shards or 8),
            chunk_size=self.spec.chunk_size,
            scratch_dir=self.spec.scratch_dir, backend=cfg.backend,
            dtype=cfg.dtype)
        self._bsizes = jnp.asarray(self._store.bucket_sizes)
        self._pipeline = ShardPipeline(
            self._store, cache_bytes=self.spec.cache_bytes,
            prefetch_depth=self.spec.prefetch_depth, stats=self.stats,
            faults=self.faults, verify_checksums=self.verify_checksums)

    def build(self, points, cfg, rng):
        self.build_source(as_source(np.asarray(points)), cfg, rng)

    def run_round(self, active, seeds, seed_valid):
        results = self._alid_batch(active, seeds)
        return self._reduce(results, seed_valid)

    def prepare_round(self, seeds) -> None:
        """Round-level overlap: fetch the NEXT round's seed rows (a
        scattered source read) and upload them in the background while the
        CURRENT round's shards stream. The driver calls this with its
        speculative seed batch; `_alid_batch` consumes the prepared rows
        only when the batch it receives matches bit-for-bit, so a resampled
        round simply falls back to the inline fetch."""
        if self._executor is None:
            import concurrent.futures
            self._executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="alid-seed-prefetch")
        seeds_np = np.array(seeds, copy=True)

        def fetch(idx=seeds_np):
            rows = np.asarray(self._store.source.sample(idx), np.float32)
            return jax.device_put(rows)

        self._prepared.append((seeds_np, self._executor.submit(fetch)))
        del self._prepared[:-2]     # current round + one speculation ahead

    def close(self) -> None:
        """Release everything fit left device-live or on disk: the slot
        ring / double buffer and host LRU, the seed-prefetch executor, and
        the scratch memmap (unlinked). Invoked by the `fit` driver on the
        way out; idempotent."""
        self._prepared.clear()
        if self._executor is not None:
            self._executor.shutdown(wait=True)
            self._executor = None
        if self._pipeline is not None:
            self._pipeline.release()
        store = self._store
        if store is not None and store.scratch is not None:
            store.scratch.close()

    # -- internals ---------------------------------------------------------
    def _seed_rows(self, seeds) -> jax.Array:
        seeds_np = np.asarray(seeds)
        for i, (prep_np, fut) in enumerate(self._prepared):
            if np.array_equal(prep_np, seeds_np):
                # drop older entries too — rounds only move forward, so an
                # unconsumed elder (an invalidated speculation) cannot match
                # any future batch
                self._prepared = self._prepared[i + 1:]
                self.stats.add("seed_prefetch_hits")
                return fut.result()
        # no match: an invalidated speculation (the driver resampled and
        # re-prepared, so its stale sibling simply ages out of the list)
        # or the very first round, which nothing preceded
        self.stats.add("seed_prefetch_misses")
        return jnp.asarray(self._store.source.sample(seeds_np), jnp.float32)

    def _route(self, roi, p: float) -> np.ndarray:
        """(B, S) ball-intersection routing matrix, evaluated on HOST from
        the store's f64 metadata. Conservative exactly like the in-jit test:
        a skipped (lane, shard) pair contains no point inside that lane's
        ROI ball, so skipping cannot change the retrieved set."""
        store = self._store
        if p != 2.0:
            return np.ones((roi.radius.shape[0], store.n_shards), bool)
        with span("alid.stream.wait"):
            cen = np.asarray(roi.center, np.float64)      # (B, d)
            rad = np.asarray(roi.radius, np.float64)      # (B,)
        dist = np.sqrt(
            ((cen[:, None, :] - store.centers[None]) ** 2).sum(-1))
        reach = rad[:, None] + store.radii[None]
        return dist <= reach + _ROUTE_EPS * (1.0 + reach)

    def _alid_batch(self, active, seeds) -> SeedResult:
        cfg, store, k = self._cfg, self._store, self.k
        b, d = int(seeds.shape[0]), store.dim
        probe = cfg.lsh.probe

        state = _init_states_batch(self._seed_rows(seeds), seeds, cfg.cap,
                                   cfg.dtype)
        c_np = np.ones((b,), np.int64)
        done_np = np.zeros((b,), bool)
        overflow_np = np.zeros((b,), bool)

        while True:
            lane_np = (~done_np) & (c_np <= cfg.c_outer)
            if not lane_np.any():
                break
            with span("alid.stream.iter"):
                new_state = _lid_batch(state, k, cfg)
                roi = _roi_batch(new_state, k, jnp.asarray(c_np, jnp.int32),
                                 cfg)
                sup_idx, sup_v, sup_x, sup_mask, ovf = _civs_begin_batch(
                    new_state, cfg)

                keys, salts = _hash_queries_batch(
                    sup_v, store.proj, store.bias, cfg.lsh.seg_len,
                    cfg.backend)
                # frozen lanes' results are discarded by the lane select
                # below, so don't let their stale ROIs force shard uploads
                with span("alid.stream.route"):
                    touch = self._route(roi, cfg.p) & lane_np[:, None]
                    routed = np.flatnonzero(touch.any(axis=0))
                if trace.recording():
                    trace.count("alid.stream.shards_routed", routed.size)
                    trace.count("alid.stream.shards_total", store.n_shards)
                    trace.count("alid.stream.lane_shard_pairs",
                                int(touch.sum()))
                carry = _init_carry_batch(b, cfg.delta, d, cfg.dtype)
                if routed.size:
                    # global probe windows, carved on host from the host
                    # tables — ROUTED shards only: an untouched shard holds no
                    # point inside any lane's ROI ball, so its bucket members
                    # could never survive the ROI filter; spending the probe
                    # budget on the reachable shards alone keeps
                    # min(bucket∩routed, probe) candidates and skips the S−T
                    # unused searchsorted passes
                    with span("alid.stream.windows"):
                        with span("alid.stream.wait"):
                            keys_np = np.asarray(keys)
                            salts_np = np.asarray(salts)
                        n_tables, q = keys_np.shape[1], keys_np.shape[2]
                        flat = (n_tables, b * q)
                        st, lo, hi = shard_bucket_windows_host(
                            store.sorted_keys[routed],
                            keys_np.transpose(1, 0, 2).reshape(flat),
                            salts_np.transpose(1, 0, 2).reshape(flat), probe)
                        # (T, L, B*q) -> (T, B, L, q)
                        st, lo, hi = (
                            a.reshape(-1, n_tables, b, q).transpose(0, 2, 1, 3)
                            for a in (st, lo, hi))

                    # stream the routed shards through the pipeline
                    # (prefetched bundles arrive in routed order, so the
                    # carry folds are identical to the synchronous path)
                    for pos, s, bundle in self._pipeline.stream(routed):
                        pts_s, sk, pm, gmap = bundle
                        with span("alid.stream.chunk", self.stats,
                                  "compute_s"):
                            carry = _stream_chunk_batch(
                                carry, pts_s, sk, pm, gmap, keys,
                                jnp.asarray(st[pos]), jnp.asarray(lo[pos]),
                                jnp.asarray(hi[pos]), roi.center,
                                roi.radius, active, sup_idx, sup_mask,
                                jnp.asarray(touch[:, s]), probe, cfg.p,
                                cfg.backend, cfg.dtype)
                    del pts_s, sk, pm, gmap, bundle, st, lo, hi
                psi_idx, psi_valid, psi_v, n_cand = _finalize_batch(carry)

                res = _civs_finish_batch(new_state, sup_idx, sup_v, sup_x,
                                         sup_mask, psi_idx, psi_valid, psi_v,
                                         k, n_cand, ovf, cfg)
                grown = roi.radius >= cfg.stop_frac * roi.r_out
                with span("alid.stream.wait"):
                    new_done = np.asarray((~res.infective_found)
                                          & (grown | (res.n_candidates == 0)))

                state = _select_lanes(jnp.asarray(lane_np), res.state, state)
                with span("alid.stream.wait"):
                    overflow_np |= lane_np & np.asarray(res.overflow)
                done_np = np.where(lane_np, new_done & (c_np > 1), done_np)
                c_np = np.where(lane_np, c_np + 1, c_np)
                # drop this iteration's device intermediates NOW — otherwise
                # a second generation stays live until the next iteration
                # rebinds the names, doubling the O(cap) working set this
                # engine exists to bound
                del new_state, roi, sup_idx, sup_v, sup_x, sup_mask, carry
                del psi_idx, psi_valid, psi_v, n_cand, res, grown, keys
                del salts

        state = _lid_batch(state, k, cfg)   # final polish, as alid_from_seed
        return _seed_results_batch(state, jnp.asarray(c_np, jnp.int32),
                                   jnp.asarray(overflow_np), cfg)


_ENGINES = {
    "replicated": ReplicatedEngine,
    "sharded": ShardedEngine,
    "mesh": MeshEngine,
    "streamed": StreamedEngine,
}


def make_engine(spec: EngineSpec) -> Engine:
    """Instantiate the engine an EngineSpec names (unbuilt)."""
    try:
        return _ENGINES[spec.engine](spec)
    except KeyError:
        raise ValueError(
            f"unknown engine {spec.engine!r}; expected one of "
            f"{sorted(_ENGINES)}") from None


# ------------------------------------------------------------- the driver --
def _save_fit_checkpoint(ckpt_dir: str, rounds: int, labels, active_np, rng,
                         seeds, seed_valid, any_eligible, densities,
                         sup_idx, sup_w, sup_v, next_label: int,
                         cap: int, d: int) -> None:
    """Persist the driver's round-level state (the resume point after round
    `rounds`). Everything the loop reads next round is here: the labels +
    active mask, the PRNG chain value, the ALREADY-SAMPLED next-round seed
    batch (seeds are drawn one round ahead for speculation, so saving the
    key alone would replay the wrong schedule), and the peeled supports."""
    from repro.checkpoint.manager import save_checkpoint
    tree = {
        "labels": labels,
        "active": active_np,
        "rng": np.asarray(rng),
        "seeds": np.asarray(seeds),
        "seed_valid": np.asarray(seed_valid),
        "densities": np.asarray(densities, np.float32),
        "sup_idx": (np.stack(sup_idx) if sup_idx
                    else np.zeros((0, cap), np.int32)),
        "sup_w": (np.stack(sup_w) if sup_w
                  else np.zeros((0, cap), np.float32)),
        "sup_v": (np.stack(sup_v).astype(np.float32) if sup_v
                  else np.zeros((0, cap, d), np.float32)),
    }
    save_checkpoint(ckpt_dir, rounds, tree, metadata={
        "kind": "alid-fit", "round": int(rounds),
        "next_label": int(next_label), "any_eligible": bool(any_eligible),
        "n": int(labels.shape[0])})


def _restore_fit_checkpoint(ckpt_dir: str):
    """Latest INTACT fit checkpoint: steps are tried newest-first, and a
    step whose bytes fail their crc32 (or cannot be read at all) is skipped
    with a warning — a torn/corrupt latest checkpoint degrades to the one
    before it instead of aborting the resume."""
    from repro.checkpoint.manager import (CheckpointCorruption,
                                          list_checkpoints,
                                          restore_checkpoint_tree)
    for step in reversed(list_checkpoints(ckpt_dir)):
        try:
            manifest, tree = restore_checkpoint_tree(ckpt_dir, step)
        except (CheckpointCorruption, OSError, KeyError, ValueError) as exc:
            warnings.warn(
                f"fit checkpoint step {step} is unusable ({exc}); falling "
                "back to the previous one", RuntimeWarning)
            continue
        if manifest.get("metadata", {}).get("kind") != "alid-fit":
            raise ValueError(
                f"checkpoint step {step} in {ckpt_dir!r} is not a fit-driver "
                f"checkpoint (kind="
                f"{manifest.get('metadata', {}).get('kind')!r})")
        return manifest, tree
    return None, None


def fit(data, cfg: ALIDConfig = ALIDConfig(),
        rng: Optional[jax.Array] = None,
        engine: Optional[Engine] = None, *,
        retry_policy: Optional[RetryPolicy] = DEFAULT_RETRY,
        checkpoint_dir: Optional[str] = None, checkpoint_every: int = 1,
        resume: bool = False, crash_at_round: int = 0) -> Clustering:
    """Dominant-cluster detection: THE host peel-reduce loop (Sec. 4.4).

    `data` is a `DataSource` (InMemorySource / MemmapSource / ChunkedSource,
    see `repro.core.source`) or a legacy (n, d) array, which is auto-wrapped
    — the driver itself only touches rows through the source interface, so
    with `EngineSpec(engine="streamed")` a memmapped dataset never
    materializes in host or device memory.

    Rounds of batched seeds (sampled from large LSH buckets) run on the
    engine `cfg.spec` selects; claims resolve through `resolve_claims`;
    claimed points + seeds are peeled until no dominant-cluster candidate
    remains (or, with cfg.exhaustive, no active point at all). All engines
    consume rng identically, so on tie-free data the engine choice does not
    change the clustering.

    Round-level overlap: while round r runs, the driver SPECULATIVELY
    samples round r+1's seeds against `active` minus round r's seed batch
    and announces them to the engine (`prepare_round` — the streamed engine
    fetches + uploads the seed rows in the background while its shards
    stream). The speculation is exact, not approximate: peeling only ever
    LOWERS seed-sampling scores (deactivated points drop to -inf), so the
    Gumbel top-k is unchanged unless one of the speculated winners itself
    got claimed — which the driver checks, resampling with the true active
    mask (same PRNG key) on a hit. Labels are therefore bit-identical to
    the sequential schedule on every engine.

    Pass a pre-made `engine` to keep it alive after fit returns (e.g. to
    read `StreamedEngine.stats`) — the caller then owns `engine.close()`;
    otherwise the driver builds one from `cfg.spec` and closes it on the
    way out (releasing the streamed engine's device slots, cache, scratch
    file, and worker threads).

    Resilience (DESIGN.md §11): the source is wrapped so every read —
    build chunks, seed rows, support gathers, the shard-prefetch reader —
    retries transient `OSError`s under `retry_policy` (None disables).
    With `checkpoint_dir` set, the driver persists its round-level state
    every `checkpoint_every` rounds through `checkpoint/manager.py`;
    `resume=True` restores the latest intact checkpoint and continues,
    producing labels BIT-IDENTICAL to the uninterrupted run (the engine
    rebuild is deterministic — same rng, same store — and the saved state
    includes the already-sampled next-round seed batch, so the PRNG
    schedule replays exactly). `crash_at_round=r` raises at the START of
    round r — the deterministic mid-fit crash used by the chaos tests.

    Returns a `Clustering` carrying per-cluster weighted supports, so the
    result can `predict` new points and serialize without the dataset.
    """
    with span("alid.fit"):
        source = resilient(as_source(data), retry_policy)
        rng = jax.random.PRNGKey(0) if rng is None else rng

        owns_engine = engine is None
        if engine is None:
            engine = make_engine(cfg.spec)
        rng, kb = jax.random.split(rng)
        with span("alid.build"):
            engine.build_source(source, cfg, kb)
            bsizes_np = np.asarray(engine.bucket_sizes)
        try:
            return _fit_loop(source, cfg, rng, engine, bsizes_np,
                             checkpoint_dir=checkpoint_dir,
                             checkpoint_every=max(1, int(checkpoint_every)),
                             resume=resume,
                             crash_at_round=int(crash_at_round))
        finally:
            if owns_engine:
                engine.close()


def _fit_loop(source: DataSource, cfg: ALIDConfig, rng: jax.Array,
              engine: Engine, bsizes_np: np.ndarray,
              checkpoint_dir: Optional[str] = None,
              checkpoint_every: int = 1, resume: bool = False,
              crash_at_round: int = 0) -> Clustering:
    """The peel loop of `fit`, over a built engine whose bucket sizes the
    host holds as `bsizes_np`. Each iteration is one `alid.round` span
    (the last may only find that no seed is left); while a profile is
    being taken it also counts seeds, accepted clusters, resamples and the
    ALID iterations of the seed lanes (`repro.utils.trace`)."""
    n = source.n
    bsizes = engine.bucket_sizes
    stats = getattr(engine, "stats", None)
    cap, d = cfg.cap, source.dim

    restored = None
    if resume:
        if checkpoint_dir is None:
            raise ValueError("fit(resume=True) needs checkpoint_dir=...")
        manifest, tree = _restore_fit_checkpoint(checkpoint_dir)
        if manifest is not None:
            meta = manifest["metadata"]
            if int(meta["n"]) != n:
                raise ValueError(
                    f"checkpoint in {checkpoint_dir!r} was written for "
                    f"n={meta['n']} points, this fit has n={n}")
            restored = (meta, tree)

    if restored is not None:
        meta, tree = restored
        labels = np.asarray(tree["labels"], np.int32)
        active_np = np.asarray(tree["active"], bool)
        active = jnp.asarray(active_np)
        # the restored PRNG value REPLACES the local chain: the build split
        # already happened (deterministically) in fit(), and the saved key
        # is the post-round-r chain value of the original run
        rng = jnp.asarray(tree["rng"])
        seeds = jnp.asarray(tree["seeds"])
        seed_valid = jnp.asarray(tree["seed_valid"])
        densities = [float(x) for x in tree["densities"]]
        sup_idx = [np.asarray(r, np.int32) for r in tree["sup_idx"]]
        sup_w = [np.asarray(r, np.float32) for r in tree["sup_w"]]
        sup_v = [np.asarray(r, np.float32) for r in tree["sup_v"]]
        next_label = int(meta["next_label"])
        any_eligible = bool(meta["any_eligible"])
        start_round = int(meta["round"])
    else:
        active_np = np.ones((n,), bool)
        active = jnp.asarray(active_np)
        labels = np.full((n,), -1, np.int32)
        densities = []
        sup_idx = []
        sup_w = []
        sup_v = []
        next_label = 0
        start_round = 0

        rng, kr = jax.random.split(rng)
        seeds, seed_valid, any_eligible = _sample_seeds(active, bsizes, kr,
                                                        cfg)
        any_eligible = bool(any_eligible)
    rounds = start_round

    for rounds in range(start_round + 1, cfg.max_rounds + 1):
        with span("alid.round", round=rounds) as round_span:
            if crash_at_round and rounds == crash_at_round:
                raise RuntimeError(f"injected crash at round {rounds}")
            with span("alid.round.wait"):
                seeds_np = np.asarray(seeds)
                valid_np = np.asarray(seed_valid)
            n_valid = int(valid_np.sum())
            round_span.annotate(seeds_valid=n_valid)
            if not n_valid:
                break
            if not cfg.exhaustive and not any_eligible:
                break
            peeled_seeds = seeds_np[valid_np]
            trace.count("alid.seeds_valid", n_valid)

            # ---- speculative round r+1 sampling, launched BEFORE round r
            # runs: the seeds themselves are guaranteed to peel, claims are
            # not known yet — validated against the actual claims below
            with span("alid.round.sample"):
                rng, kr_next = jax.random.split(rng)
                spec_active = active.at[jnp.asarray(peeled_seeds)].set(False)
                spec_seeds, spec_valid, _ = _sample_seeds(spec_active, bsizes,
                                                          kr_next, cfg)
                engine.prepare_round(spec_seeds)
                if stats is not None:
                    stats.add("rounds_speculated")

            with span("alid.round.launch"):
                claimed, best_row, results = engine.run_round(active, seeds,
                                                              seed_valid)

            with span("alid.round.wait"):
                claimed_np = np.asarray(claimed)
                row_np = np.asarray(best_row)
                dens_np = np.asarray(results.density)
                member_np = np.asarray(results.member_idx)
                weight_np = np.asarray(results.member_w)
                # a lane's own ALID iterations against the batch's slowest,
                # which every lane of the vmapped loop executes
                n_outer = (np.asarray(results.n_outer) if trace.recording()
                           else None)
            if n_outer is not None:
                executed = n_outer.size * int(n_outer.max())
                trace.count("alid.lane_iters_useful",
                            int(n_outer[valid_np].sum()))
                trace.count("alid.lane_iters_executed", executed)
                # every CIVS pass looks up each support slot in each table
                trace.count(engine.probe_counter,
                            executed * cfg.a_cap * cfg.lsh.n_tables)
            # peel everything claimed + the seeds themselves (guarantees
            # progress); done FIRST so next round's seeds finalize — and the
            # engine's background seed fetch keeps running — while the label
            # bookkeeping below touches the source
            new_inactive = claimed_np.copy()
            new_inactive[peeled_seeds] = True
            active_np &= ~new_inactive
            active = jnp.asarray(active_np)

            # ---- validate the speculation: exact unless a speculated winner
            # was claimed away (scores elsewhere only dropped to -inf, which
            # cannot change a Gumbel top-k it did not win)
            with span("alid.round.resample"):
                spec_seeds_np = np.asarray(spec_seeds)
                if claimed_np[spec_seeds_np[np.asarray(spec_valid)]].any():
                    spec_seeds, spec_valid, _ = _sample_seeds(
                        active, bsizes, kr_next, cfg)
                    engine.prepare_round(spec_seeds)
                    trace.count("alid.rounds_resampled")
                    if stats is not None:
                        stats.add("rounds_resampled")
                seeds, seed_valid = spec_seeds, spec_valid
                any_eligible = bool((active_np
                                     & (bsizes_np > cfg.min_bucket)).any())

            # Assign labels for winning rows that clear the density
            # threshold — ONE segment pass (stable argsort groups claimed
            # points by winning row; np.unique yields the rows in ascending
            # order, matching the label numbering of the historical per-row
            # Python loop, which was O(rounds·seeds) host work and would
            # bottleneck streamed rounds).
            with span("alid.round.label"):
                claimed_pts = np.where(claimed_np)[0]
                grp = np.argsort(row_np[claimed_pts], kind="stable")
                sorted_pts = claimed_pts[grp]
                uniq_rows, counts = np.unique(row_np[claimed_pts],
                                              return_counts=True)
                keep = (dens_np[uniq_rows] >= cfg.density_min) & (counts > 1)
                n_keep = int(keep.sum())
                lab = np.full(uniq_rows.shape[0], -1, np.int32)
                lab[keep] = next_label + np.arange(n_keep, dtype=np.int32)
                labels[sorted_pts] = np.repeat(lab, counts)
                for row in uniq_rows[keep]:
                    densities.append(float(dens_np[row]))
                    midx, mw = member_np[row], weight_np[row]
                    valid = (midx >= 0) & (mw > 0)
                    w = np.where(valid, mw, 0.0).astype(np.float32)
                    w /= max(float(w.sum()), 1e-12)
                    sup_idx.append(np.where(valid, midx, -1).astype(np.int32))
                    sup_w.append(w)
                    sup_v.append(np.asarray(
                        source.sample(np.clip(midx, 0, n - 1)), np.float32)
                        * valid[:, None])
                next_label += n_keep
            trace.count("alid.clusters_accepted", n_keep)
            if not active_np.any():
                break
            # round-level resume point — saved only when the loop continues,
            # so a resumed run re-enters at round+1 exactly where the
            # uninterrupted run did (crashing AFTER the final round just
            # re-runs it, which is deterministic and lands on the same
            # labels)
            if checkpoint_dir is not None and rounds % checkpoint_every == 0:
                with span("alid.round.checkpoint"):
                    _save_fit_checkpoint(checkpoint_dir, rounds, labels,
                                         active_np, rng, seeds, seed_valid,
                                         any_eligible, densities, sup_idx,
                                         sup_w, sup_v, next_label, cap, d)

    with span("alid.finish"):
        return Clustering(
            labels=labels,
            densities=np.asarray(densities, np.float32),
            n_rounds=rounds,
            k=float(engine.k),
            support_idx=(np.stack(sup_idx) if sup_idx
                         else np.zeros((0, cap), np.int32)),
            support_w=(np.stack(sup_w) if sup_w
                       else np.zeros((0, cap), np.float32)),
            support_v=(np.stack(sup_v).astype(np.float32) if sup_v
                       else np.zeros((0, cap, d), np.float32)),
        )
