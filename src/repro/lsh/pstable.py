"""p-stable Locality Sensitive Hashing (Datar et al., SoCG'04) in pure JAX.

The paper's CIVS step indexes all data items with LSH. A CPU implementation
chains hash buckets in a hash map; that is hostile to TPUs, so we realize each
table as ONE sorted permutation of the dataset keyed by a 32-bit mixed bucket
key. A query by an arbitrary vector = binary search (searchsorted) + a bounded
contiguous gather, which is fixed-shape and fully vectorizable / vmappable —
the TPU-native analogue of walking a bucket's chain. A query by a DATA row
(every CIVS query is one) skips the search: the build keeps each point's
bucket head and size per table (`LSHTables.directory`), so the probe is one
directory row read plus one contiguous slice of `perm` per table.

h_{l,j}(v) = floor((w_{l,j} . v + b_{l,j}) / r)   w ~ N(0,1)  (p=2 stable)
key_l(v)  = mix32(h_{l,1..m})                     (multiply-xor fold)
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops


class LSHParams(NamedTuple):
    n_tables: int = 4          # L
    n_projections: int = 8     # mu (hash functions per table)
    seg_len: float = 1.0       # r, the quantization segment length (paper Fig.6)
    probe: int = 16            # max neighbours gathered per table per query


class LSHTables(NamedTuple):
    """Monolithic tables over n points.

    `directory` is the per-point bucket directory: row i holds point i's
    bucket head (its bucket's first position in `sorted_keys[l]`) for each
    table l, then its bucket size for each table, so a data row's probe
    reads one (2L,) row instead of searching. It costs 8·L bytes a point
    (3.2 MB at n = 100,000, L = 4: ~6% of the points at d = 128 in f32).
    """
    proj: jax.Array         # (L, m, d)
    bias: jax.Array         # (L, m)
    sorted_keys: jax.Array  # (L, n) uint32, ascending per table
    perm: jax.Array         # (L, n) int32: position in sorted order -> data index
    directory: jax.Array    # (n, 2L) int32: [head_0..head_{L-1}, size_0..size_{L-1}]


class ShardedLSHTables(NamedTuple):
    """Shard-local LSH: one sorted key array per (shard, table).

    The projections/biases are SHARED across shards, so a query hashes once
    and the same (key, salt) probes every shard — the per-shard tables are an
    exact partition of the monolithic table's buckets. Padded slots carry
    `PAD_KEY` (sorts last) and perm -1 (never returned as a hit).
    """
    proj: jax.Array         # (L, m, d) — shared across shards
    bias: jax.Array         # (L, m)
    sorted_keys: jax.Array  # (S, L, cap) uint32, ascending per (shard, table)
    perm: jax.Array         # (S, L, cap) int32: sorted pos -> LOCAL slot, -1 pad


PAD_KEY = jnp.uint32(0xFFFFFFFF)


_MIX_MUL = jnp.uint32(0x9E3779B1)  # analysis: allow(private-lsh): golden-ratio Weyl constant for the host-side salt fold below — table seeds, not per-point bucket keys (those route through ops.lsh_hash)


def _mix_fold(h: jax.Array) -> jax.Array:
    """Fold (.., m) int32 lattice coords into (..,) uint32 bucket keys."""
    # analysis: allow(private-lsh): FNV offset basis seeds the salt fold — multi-table seed mixing, not the point hash kernel
    acc = jnp.full(h.shape[:-1], jnp.uint32(0x811C9DC5))
    hu = h.astype(jnp.uint32)
    for j in range(h.shape[-1]):
        acc = (acc ^ hu[..., j]) * _MIX_MUL
        acc = acc ^ (acc >> jnp.uint32(15))
    return acc


def make_projections(rng: jax.Array, params: LSHParams, d: int,
                     dtype) -> tuple[jax.Array, jax.Array]:
    """The ONE place the PRNG key becomes (proj, bias).

    Every consumer (monolithic build, sharded build, the store's spatial
    ordering) must derive identical projections from the same key — that
    bit-equality is what makes sharded retrieval an exact re-chunking of
    replicated retrieval (DESIGN.md §3.1) — so none of them may inline this
    recipe.
    """
    k_proj, k_bias = jax.random.split(rng)
    proj = jax.random.normal(
        k_proj, (params.n_tables, params.n_projections, d), dtype)
    bias = jax.random.uniform(
        k_bias, (params.n_tables, params.n_projections), dtype,
        0.0, params.seg_len)
    return proj, bias


def hash_points(v: jax.Array, proj: jax.Array, bias: jax.Array,
                seg_len: float, backend: str = "auto") -> jax.Array:
    """Keys for v:(n,d) under all tables -> (L, n) uint32.

    Routed through `repro.kernels.ops.lsh_hash` (the projection einsum +
    floor-quantize + multiply-xor fold, f32-cast regardless of input dtype —
    one convention shared with the Pallas kernel, so f32 and bf16 sources
    produce bit-identical keys and Sharded/Streamed store key identity holds
    by construction). The einsum rounds per element over rows, so chunked
    hashing (`hash_chunk`) equals a monolithic pass bit-for-bit.
    """
    keys = ops.lsh_hash(v, proj, bias, seg_len, backend=backend)   # (n, L)
    return jax.lax.bitcast_convert_type(keys, jnp.uint32).T


@functools.partial(jax.jit, static_argnames=("params", "backend"))
def build_lsh(v: jax.Array, params: LSHParams, rng: jax.Array,
              backend: str = "auto") -> LSHTables:
    n, d = v.shape
    # projections are pinned f32 regardless of point storage dtype: bf16
    # random normals would be DIFFERENT values, silently breaking the
    # cross-engine key-identity argument for mixed-precision stores
    proj, bias = make_projections(rng, params, d, jnp.float32)
    keys = hash_points(v, proj, bias, params.seg_len, backend)  # (L, n)
    order = jnp.argsort(keys, axis=1).astype(jnp.int32)          # (L, n)
    sorted_keys = jnp.take_along_axis(keys, order.astype(jnp.int32), axis=1)
    return LSHTables(proj=proj, bias=bias, sorted_keys=sorted_keys, perm=order,
                     directory=_bucket_directory(sorted_keys, order))


def _bucket_directory(sorted_keys: jax.Array, perm: jax.Array) -> jax.Array:
    """(n, 2L) per-point bucket heads and sizes from the sorted tables.

    A sorted slot starts a bucket where its key differs from the previous
    slot's; a running max of those start positions gives every slot its
    bucket's head. The same running max over the reversed tables gives the
    reversed position of the bucket's last slot, hence its end. Each column
    is then filed under the points' data indices by a scatter through
    `perm`. (One (2L, n) running max and 1-D scatters because the TPU
    compiler spends over a minute on a reverse running min at n = 100,000
    and ~25 s on one (n, 2L) scatter at n = 1,000,000; these forms
    compile in seconds.)
    """
    n_tables, n = sorted_keys.shape
    pos = jnp.arange(n, dtype=jnp.int32)
    change = sorted_keys[:, 1:] != sorted_keys[:, :-1]            # (L, n-1)
    edge = jnp.ones((n_tables, 1), bool)
    starts = jnp.concatenate([edge, change], axis=1)              # (L, n)
    lasts = jnp.flip(jnp.concatenate([change, edge], axis=1), axis=1)
    run = jax.lax.cummax(jnp.where(jnp.concatenate([starts, lasts]), pos, 0),
                         axis=1)                                  # (2L, n)
    head = run[:n_tables]
    end = n - jnp.flip(run[n_tables:], axis=1)
    cols = jnp.concatenate([head, end - head])                    # (2L, n)
    return jnp.stack([
        jnp.zeros((n,), jnp.int32).at[perm[c % n_tables]].set(
            cols[c], unique_indices=True)
        for c in range(2 * n_tables)], axis=1)


def _query_one_table(sorted_keys: jax.Array, perm: jax.Array, key: jax.Array,
                     salt: jax.Array, probe: int):
    """Return up to `probe` data indices whose key matches (else -1).

    Large buckets hold more members than `probe`; starting every gather at the
    bucket head would make all queries into the same bucket return identical
    candidates (poor CIVS coverage). A per-query salt spreads the probe window
    pseudo-randomly across the bucket, so the paper's multi-query coverage
    argument (Fig. 4b) holds even when all support points share one bucket.
    """
    start = jnp.searchsorted(sorted_keys, key, side="left")
    end = jnp.searchsorted(sorted_keys, key, side="right")
    offset = _window_offset(end - start, salt, probe)
    offs = jnp.arange(probe)
    pos = jnp.minimum(start + offset + offs, sorted_keys.shape[0] - 1)
    # every slot of [start, end) holds `key`, so the bound is the whole test
    return jnp.where(start + offset + offs < end, perm[pos], -1)


def _window_offset(size: jax.Array, salt: jax.Array, probe: int) -> jax.Array:
    """Where a bucket of `size` members starts its `probe`-wide window:
    salt % (size - probe + 1) when the bucket is larger, else its head."""
    span = jnp.maximum(size - probe, 0)
    return jnp.where(span > 0,
                     (salt % (span.astype(jnp.uint32) + 1)).astype(size.dtype),
                     0)


def hash_queries(q: jax.Array, proj: jax.Array, bias: jax.Array,
                 seg_len: float,
                 backend: str = "auto") -> tuple[jax.Array, jax.Array]:
    """(keys, salts) for queries q:(Q,d) -> both (L, Q) uint32.

    Keys come from `ops.lsh_hash` — the same op that hashed the data points,
    so a support row queried back lands in its own bucket bit-for-bit on
    every backend. The per-query salt comes from the raw float bits of the
    projections: ANY two distinct points get different salts, so their probe
    windows differ even inside one giant bucket (CIVS coverage, Fig. 4b).
    The salt projection is recomputed locally (f32, matching the key
    convention) — query batches are a_cap-sized (B·a_cap under the streamed
    engine's vmap), so the duplicate (Q,d)x(L,m,d) einsum stays noise next
    to the shard probes it guards; folding salts into the hash kernel would
    force every backend to emit the pre-fold z, a (Q, L, m) HBM round-trip
    the fused kernel exists to avoid.
    """
    keys = hash_points(q, proj, bias, seg_len, backend)              # (L, Q)
    return keys, query_salts(q, proj, bias)


def query_salts(q: jax.Array, proj: jax.Array, bias: jax.Array) -> jax.Array:
    """Per-query probe-window salts for q:(Q,d) -> (L, Q) uint32: the
    multiply-xor fold of the raw f32 bits of the query's projections
    (`hash_queries` documents why)."""
    # analysis: allow(private-matmul): duplicate salt projection documented in hash_queries — fusing it into the hash kernel would force a (Q, L, m) HBM round-trip
    z = (jnp.einsum("nd,lmd->lnm", q.astype(jnp.float32),
                    proj.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
         + bias[:, None, :].astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(z, jnp.uint32)
    return _mix_fold(jax.lax.bitcast_convert_type(bits, jnp.int32))


def shard_bucket_windows(sorted_keys: jax.Array, keys: jax.Array,
                         salts: jax.Array, probe: int):
    """Global probe budget: split one `probe`-wide window across shards.

    sorted_keys: (S, L, cap) per-shard tables; keys/salts: (L, Q) pre-hashed
    queries. For every (table, query) the GLOBAL bucket is the concatenation
    of the per-shard buckets (shards partition the dataset and share hash
    functions), so a single contiguous window of `probe` slots — placed at
    the same salted offset formula `_query_one_table` uses — is carved out of
    that concatenation and intersected with each shard's span. The union over
    shards then retrieves exactly `min(global bucket size, probe)` members,
    matching the replicated engine's sample SIZE even when one oversized
    bucket spans many shards (per-shard windows would return up to S*probe).

    Returns (starts, lo, hi), each (S, L, Q) int32: `starts` is the bucket
    head inside the shard's sorted order; the shard retrieves local bucket
    positions [lo, hi).
    """
    def per_shard(sk):                                    # sk: (L, cap)
        s = jax.vmap(lambda a, k: jnp.searchsorted(a, k, side="left"))(sk, keys)
        e = jax.vmap(lambda a, k: jnp.searchsorted(a, k, side="right"))(sk, keys)
        return s, e

    starts, ends = jax.vmap(per_shard)(sorted_keys)       # (S, L, Q)
    sizes = ends - starts
    total = jnp.sum(sizes, axis=0)                        # (L, Q)
    prefix = jnp.cumsum(sizes, axis=0) - sizes            # members in shards < s
    span = jnp.maximum(total - probe, 0)
    offset = (salts % (span.astype(jnp.uint32) + 1)).astype(sizes.dtype)
    lo = jnp.clip(offset[None] - prefix, 0, sizes)
    hi = jnp.clip(offset[None] + probe - prefix, 0, sizes)
    return starts, lo, hi


@functools.partial(jax.jit, static_argnames=("seg_len", "backend"))
def hash_chunk(chunk: jax.Array, proj: jax.Array, bias: jax.Array,
               seg_len: float,
               backend: str = "auto") -> tuple[jax.Array, jax.Array]:
    """Bucket keys + spatial-ordering score for ONE host chunk of rows.

    The streamed store build (`store.build_store_streamed`) hashes the
    dataset chunk-by-chunk through this: `keys` (L, m) are the same einsum +
    floor + mix as `hash_points` — per-element over rows, so chunked keys are
    bit-identical to a monolithic `build_lsh` pass — and `score` (m,) is the
    projection onto the first LSH direction, the ordering `_build_store_impl`
    shards by. Only O(chunk) rows are ever device-resident.
    """
    keys = hash_points(chunk, proj, bias, seg_len, backend)
    with jax.default_matmul_precision("highest"):   # f32 on every backend
        score = chunk @ proj[0, 0]
    return keys, score


def shard_bucket_windows_host(sorted_keys, keys, salts, probe: int):
    """Numpy mirror of `shard_bucket_windows` for HOST-resident shard tables.

    sorted_keys: (S, L, cap) uint32 numpy; keys/salts: (L, Q) uint32 numpy.
    Integer-for-integer identical to the jax version (searchsorted + the same
    salted-offset formula in uint32), so a host-streamed driver carves the
    exact same global probe windows as the in-jit sharded engine — without
    ever shipping the (S, L, cap) key tables to device.
    Returns (starts, lo, hi), each (S, L, Q) int32.
    """
    import numpy as np

    s_n, l_n, _ = sorted_keys.shape
    q_n = keys.shape[1]
    starts = np.empty((s_n, l_n, q_n), np.int64)
    ends = np.empty((s_n, l_n, q_n), np.int64)
    for s in range(s_n):
        for l in range(l_n):
            starts[s, l] = np.searchsorted(sorted_keys[s, l], keys[l], "left")
            ends[s, l] = np.searchsorted(sorted_keys[s, l], keys[l], "right")
    sizes = ends - starts
    total = sizes.sum(axis=0)                             # (L, Q)
    prefix = np.cumsum(sizes, axis=0) - sizes
    span = np.maximum(total - probe, 0)
    offset = (np.asarray(salts, np.uint32)
              % (span.astype(np.uint32) + np.uint32(1))).astype(np.int64)
    lo = np.clip(offset[None] - prefix, 0, sizes)
    hi = np.clip(offset[None] + probe - prefix, 0, sizes)
    return (starts.astype(np.int32), lo.astype(np.int32),
            hi.astype(np.int32))


def _window_one_table(sorted_keys: jax.Array, perm: jax.Array, key: jax.Array,
                      start: jax.Array, lo: jax.Array, hi: jax.Array,
                      probe: int) -> jax.Array:
    """Gather local bucket positions [lo, hi) (a pre-allocated sub-window of
    the global probe budget) from one shard's table; -1 on unused slots."""
    offs = jnp.arange(probe)
    pos = jnp.minimum(start + lo + offs, sorted_keys.shape[0] - 1)
    hit = (lo + offs < hi) & (sorted_keys[pos] == key)
    return jnp.where(hit, perm[pos], -1)


def probe_tables_window(sorted_keys: jax.Array, perm: jax.Array,
                        keys: jax.Array, starts: jax.Array, lo: jax.Array,
                        hi: jax.Array, probe: int) -> jax.Array:
    """Probe one shard's tables with explicit per-(table, query) windows from
    `shard_bucket_windows`. sorted_keys/perm: (L, cap); keys/starts/lo/hi:
    (L, Q) -> (Q, L*probe) local-slot indices, -1 = miss."""
    def per_table(sk, pm, kq, st, l, h):
        return jax.vmap(
            lambda k1, s1, l1, h1: _window_one_table(sk, pm, k1, s1, l1, h1,
                                                     probe))(kq, st, l, h)

    cands = jax.vmap(per_table)(sorted_keys, perm, keys, starts, lo, hi)
    return jnp.transpose(cands, (1, 0, 2)).reshape(keys.shape[1], -1)


def probe_tables(sorted_keys: jax.Array, perm: jax.Array, keys: jax.Array,
                 salts: jax.Array, probe: int) -> jax.Array:
    """Probe pre-hashed queries against one set of tables.

    sorted_keys/perm: (L, n); keys/salts: (L, Q) -> (Q, L*probe) indices in
    whatever index space `perm` holds (data indices for the monolithic
    tables, local slots for one shard), -1 = miss.
    """
    def per_table(sk, pm, kq, sq):
        return jax.vmap(lambda kk, ss: _query_one_table(sk, pm, kk, ss, probe))(kq, sq)

    cands = jax.vmap(per_table)(sorted_keys, perm, keys, salts)      # (L, Q, probe)
    return jnp.transpose(cands, (1, 0, 2)).reshape(keys.shape[1], -1)


@functools.partial(jax.jit, static_argnames=("params", "backend"))
def query_batch(tables: LSHTables, q: jax.Array, params: LSHParams,
                backend: str = "auto") -> jax.Array:
    """Candidates for queries q:(Q,d) -> (Q, L*probe) int32 data indices, -1 = miss."""
    keys, salts = hash_queries(q, tables.proj, tables.bias, params.seg_len,
                               backend)
    return probe_tables(tables.sorted_keys, tables.perm, keys, salts, params.probe)


def probe_rows(tables: LSHTables, idx: jax.Array, salts: jax.Array,
               probe: int) -> jax.Array:
    """Candidates for DATA rows idx:(Q,) -> (Q, L*probe) int32, -1 = miss.

    The same candidates `query_batch` returns for the rows `points[idx]`
    under `salts` (L, Q) (`query_salts` of those rows): a data row's key is
    the one the build sorted by, so its bucket's head and size come from
    its directory row, and each table's window is `probe` contiguous slots
    of `perm` — no hashing, no binary search, no key re-check.

    The TPU expands a gather of `probe` slots at an arbitrary offset into
    a serial loop over the queries, but gathers aligned rows natively. So
    `perm` is cut into rows of `probe` slots (padded with -1, plus one
    spare row), the two rows that hold a window are gathered, and the
    window is shifted into place by the bits of its offset in the row.
    """
    n_tables, n = tables.perm.shape
    dirs = tables.directory[jnp.clip(idx, 0, n - 1)]              # (Q, 2L)
    head, size = dirs[:, :n_tables].T, dirs[:, n_tables:].T       # (L, Q)
    offset = _window_offset(size, salts, probe)
    start = head + offset                                         # < n
    n_rows = -(-n // probe) + 1
    rows = jnp.pad(tables.perm, ((0, 0), (0, n_rows * probe - n)),
                   constant_values=-1).reshape(n_tables * n_rows, probe)
    r = (jnp.arange(n_tables, dtype=jnp.int32)[:, None] * n_rows
         + start // probe)
    win = jnp.concatenate([rows[r], rows[r + 1]], axis=-1)        # (L, Q, 2p)
    shift = start % probe
    for b in range(max(1, (probe - 1).bit_length())):
        win = jnp.where(((shift >> b) & 1)[..., None] == 1,
                        jnp.roll(win, -(1 << b), axis=-1), win)
    hit = offset[..., None] + jnp.arange(probe) < size[..., None]
    cands = jnp.where(hit, win[..., :probe], -1)                  # (L, Q, p)
    return jnp.transpose(cands, (1, 0, 2)).reshape(idx.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("params", "backend"))
def build_lsh_sharded(shard_points: jax.Array, valid: jax.Array,
                      params: LSHParams, rng: jax.Array,
                      backend: str = "auto") -> ShardedLSHTables:
    """Shard-local tables over pre-partitioned points (S, cap, d).

    Consumes `rng` exactly like `build_lsh` (via make_projections), so the
    SAME key yields the SAME projections/biases — per-point bucket keys are
    then bit-identical to the monolithic build (the einsum rounds per
    element, independent of batching), which is what makes sharded CIVS
    retrieval provably a re-chunking of the replicated retrieval rather
    than an approximation.
    """
    s, cap, d = shard_points.shape
    proj, bias = make_projections(rng, params, d, jnp.float32)  # see build_lsh
    keys = jax.vmap(
        lambda v: hash_points(v, proj, bias, params.seg_len, backend))(
        shard_points)                                         # (S, L, cap)
    keys = jnp.where(valid[:, None, :], keys, PAD_KEY)
    order = jnp.argsort(keys, axis=-1).astype(jnp.int32)
    sorted_keys = jnp.take_along_axis(keys, order, axis=-1)
    sorted_valid = jnp.take_along_axis(
        jnp.broadcast_to(valid[:, None, :], keys.shape), order, axis=-1)
    perm = jnp.where(sorted_valid, order, -1)
    return ShardedLSHTables(proj=proj, bias=bias, sorted_keys=sorted_keys,
                            perm=perm)


@jax.jit
def bucket_sizes(tables: LSHTables) -> jax.Array:
    """Per data item: size of its bucket in table 0 (used for PALID seeding —
    the paper samples initial vertexes from buckets with > 5 items), read
    from the build's bucket directory."""
    return tables.directory[:, tables.perm.shape[0]]
