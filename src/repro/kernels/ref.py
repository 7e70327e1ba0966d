"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (shape/dtype
sweeps in tests/test_kernels.py, interpret mode) and what `ops` runs for
backend="ref": the resolved mode on a non-TPU backend such as the CPU test
suite, and the plain reference a TPU run is compared with
(`chip_smoke.py`'s reference phase fits the same data on both).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MASK_VALUE = -1e30
# f32 contractions run at full f32 precision on every backend (XLA's TPU
# default for an f32 matmul is a single bf16 pass)
HIGHEST = jax.lax.Precision.HIGHEST


def tree_sum(p: jax.Array, keepdims: bool = False) -> jax.Array:
    """Sum over the last axis with a FIXED binary-tree order, in f32.

    XLA picks the association of a reduction per lowering context — a 1-D
    sum, a padded row, a gemv and a vmapped batched gemm all add the same
    terms in different orders (1-ulp drifts that broke ref-vs-interpret
    engine parity). Spelling the tree out as explicit pairwise adds over a
    zero-padded power-of-two width pins the dataflow: every backend,
    batched or not, fused or not, computes bit-identical output, and extra
    zero padding (a kernel's lane-aligned layout) only adds exact zeros in
    the top levels. Cost is log2(n) vectorized adds — no MXU needed.
    """
    p = p.astype(jnp.float32)
    n = p.shape[-1]
    size = 1 << max(n - 1, 0).bit_length()
    p = jnp.pad(p, [(0, 0)] * (p.ndim - 1) + [(0, size - n)])
    while p.shape[-1] > 1:
        half = p.shape[-1] // 2
        p = p[..., :half] + p[..., half:]
    return p if keepdims else p[..., 0]


def tree_matvec(a: jax.Array, w: jax.Array) -> jax.Array:
    """(m, n) @ (n,) through `tree_sum`'s fixed order: (m,) f32."""
    return tree_sum(a.astype(jnp.float32) * w.astype(jnp.float32)[None, :])


# ---------------------------------------------------------------- affinity --
def pairwise_distance_ref(q: jax.Array, c: jax.Array,
                          p: float = 2.0) -> jax.Array:
    """||q_i - c_j||_p in f32: (m, d), (n, d) -> (m, n).

    THE distance contraction. Every consumer — `core.affinity`'s pairwise
    distance, the CIVS ROI filter, the affinity oracles below, and the
    Pallas kernels' per-tile math — shares this one formula, so replicated /
    sharded / streamed filtering is bit-identical by construction (three
    private copies used to disagree in summation form). p=2 uses the
    MXU-friendly expansion |q|^2 + |c|^2 - 2 q c^T — the form the Pallas
    tiles compute, which is what makes ref/pallas parity possible. The
    expansion cancels for points far from the origin (abs error ~ |v|^2 *
    eps_f32, vs ~ dist * eps for the direct (q-c)^2 form), the standard
    cost of the matmul formulation; center data with |v| >> 1e2 before
    clustering if boundary-exact ROI radii matter. Other p fall back to
    broadcast abs-power (O(m*n*d) memory — small blocks only).
    """
    q32 = q.astype(jnp.float32)
    c32 = c.astype(jnp.float32)
    if p == 2.0:
        q2 = jnp.sum(q32 * q32, -1)[:, None]
        c2 = jnp.sum(c32 * c32, -1)[None, :]
        d2 = q2 + c2 - 2.0 * jnp.matmul(q32, c32.T, precision=HIGHEST)
        return jnp.sqrt(jnp.maximum(d2, 0.0))
    diff = jnp.abs(q32[:, None, :] - c32[None, :, :])
    return jnp.power(jnp.sum(jnp.power(diff, p), axis=-1), 1.0 / p)


def affinity_ref(q: jax.Array, c: jax.Array, k_scale: jax.Array,
                 p: float = 2.0) -> jax.Array:
    """exp(-k * ||q_i - c_j||_p): (m, d), (n, d) -> (m, n). No diagonal logic."""
    dist = pairwise_distance_ref(q, c, p)
    return jnp.exp(-k_scale * dist).astype(q.dtype)


def affinity_column_ref(v: jax.Array, i: jax.Array, k_scale: jax.Array,
                        p: float = 2.0) -> jax.Array:
    """exp(-k ||v_j - v_i||_p): row i of a block against the whole block,
    (cap, d), () -> (cap,) f32 — the LID sweep's on-demand column (Eq.
    13/14).

    Same |v|^2 + |c|^2 - 2 v.c expansion as `pairwise_distance_ref`, but
    every d-sum is a row reduction of the block (|v_i|^2 is entry i of the
    row norms, the cross term an elementwise product reduced over d), not
    a matvec or a 1-D sum: XLA's CPU backend associates those differently
    per batching context, while a row reduction over d is the same loop
    everywhere — so the Pallas sweep run in interpret mode stays
    bit-identical to this oracle inside the vmapped engines."""
    v32 = v.astype(jnp.float32)
    c32 = v32[i]
    if p != 2.0:
        return affinity_ref(v32, c32[None, :], k_scale, p)[:, 0]
    v2 = jnp.sum(v32 * v32, -1)
    d2 = v2 + v2[i] - 2.0 * jnp.sum(v32 * c32[None, :], -1)
    return jnp.exp(-k_scale * jnp.sqrt(jnp.maximum(d2, 0.0)))


def affinity_matvec_ref(q: jax.Array, q_idx: jax.Array, c: jax.Array,
                        c_idx: jax.Array, w: jax.Array, k_scale: jax.Array,
                        p: float = 2.0) -> jax.Array:
    """Masked affinity x weights matvec (Eq. 13/17 refresh), one pass:

        out_i = sum_j [q_idx_i != c_idx_j] * exp(-k ||q_i - c_j||) * w_j

    q:(m,d), q_idx:(m,), c:(n,d), c_idx:(n,), w:(n,) -> (m,) f32. The index
    compare realizes a_ii = 0 (and dedup defensiveness) without a separate
    mask tensor; slot-validity masks fold into `w` (c side) and a row select
    on the output (q side), so callers never materialize the (m, n) block.
    The contraction goes through `tree_matvec` (NOT `a @ w`) because this
    op's output lands in continuous results (densities via the Ax refresh),
    where context-dependent reduction order would leak into user-visible
    bits.
    """
    a = affinity_ref(q, c, k_scale, p).astype(jnp.float32)
    a = jnp.where(q_idx[:, None] == c_idx[None, :], 0.0, a)
    return tree_matvec(a, w)


def roi_filter_ref(vc: jax.Array, center: jax.Array, radius: jax.Array,
                   valid: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused ROI distance filter (CIVS step 3): distance to the ROI center,
    radius+validity mask, and neg-distance top-k scores in one pass.

    vc:(C,d), center:(d,), radius:(), valid:(C,) bool ->
    (dist (C,) f32, valid_out (C,) bool, neg (C,) f32) with
    valid_out = valid & (dist <= radius) and neg = -dist on valid_out else
    -inf (the score `jax.lax.top_k` ranks, nearest-first).

    Single-center special case: the distance is the DIRECT per-row
    sum((v - c)^2) reduction, not `pairwise_distance_ref`'s matmul
    expansion. With one center the expansion degenerates to a (C, d)x(d, 1)
    matmul plus a separate |v|^2 sweep — strictly more arithmetic than the
    fused subtract-square-reduce loop XLA emits for this form (it
    benchmarked SLOWER than the pre-fusion composition) — and the direct
    form is also the numerically tighter one (no |v|^2 cancellation). The
    Pallas tile computes the identical per-row reduction, so ref/interpret
    stay bit-aligned; cross-engine parity needs only every engine routing
    through THIS op, which they do (civs.retrieve_chunk /
    _retrieve_replicated).
    """
    vc32 = vc.astype(jnp.float32)
    cen32 = center.astype(jnp.float32)
    diff = vc32 - cen32[None, :]
    dist = jnp.sqrt(jnp.sum(diff * diff, -1))
    ok = valid & (dist <= radius)
    neg = jnp.where(ok, -dist, -jnp.inf)
    return dist, ok, neg


def lid_sweep_ref(v_beta: jax.Array, beta_idx: jax.Array,
                  beta_mask: jax.Array, x: jax.Array, ax: jax.Array,
                  n_iters: jax.Array, converged: jax.Array,
                  k_scale: jax.Array, n_steps: int, max_iters: int,
                  tol: float, p: float = 2.0, refresh_every: int = 0,
                  support_eps: float = 1e-6
                  ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused multi-iteration LID sweep (paper Sec. 4.1, Eq. 9-14): up to
    `n_steps` infection-immunization iterations over ONE seed's (cap, d)
    support block, stopping early on convergence or `n_iters == max_iters`.

    v_beta:(cap,d), beta_idx:(cap,) i32, beta_mask:(cap,) bool, x/ax:(cap,)
    f32 accumulators, n_iters:() i32 (CUMULATIVE across sweeps — the caller's
    while-over-chunks threads it through), converged:() bool ->
    (x, ax, n_iters, converged).

    Each executed step is bit-identical to one iteration of the pre-sweep
    `lid_solve` body: residual r = Ax - pi(x), C1∪C2 argmax (Eq. 6), invasion
    share eps (Eq. 9/11/12), the on-demand affinity column (Eq. 13/14), and
    the x/Ax updates. The column (the only O(cap*d) work) is gated on the
    convergence flag, so the detecting iteration is O(cap). Mixed precision:
    `v_beta` may be bf16 STORAGE — it is upcast to f32 once at entry and the
    column/accumulator math runs entirely in f32 (bf16 never re-enters).

    `refresh_every=M > 0` recomputes Ax exactly from the support (the
    `refresh_ax` masked matvec, same op order as `affinity_matvec_ref`) every
    M cumulative iterations, killing incremental f32 drift inside long
    sweeps. Default 0 = off: the incremental Eq. 14 updates are kept
    bit-identical to the historical `lid_solve` path.
    """
    # jnp coercion up front: raw numpy operands would otherwise be indexed
    # with traced argmax results inside the while_loop body
    v32 = jnp.asarray(v_beta).astype(jnp.float32)
    idx = jnp.asarray(beta_idx, jnp.int32)
    mask = jnp.asarray(beta_mask)
    k32 = jnp.asarray(k_scale, jnp.float32)
    cap = v32.shape[0]

    def step(carry):
        t, x, ax, it, _ = carry
        pi = tree_sum(x * ax)
        r = jnp.where(mask, ax - pi, 0.0)
        c1 = mask & (r > tol)
        c2 = mask & (r < -tol) & (x > 0.0)
        score = jnp.where(c1 | c2, jnp.abs(r), -jnp.inf)
        i = jnp.argmax(score)
        done = score[i] <= tol

        def update(args):
            x, ax = args
            ri = r[i]
            xi = x[i]
            mu = jnp.where(ri > 0.0, 1.0, xi / jnp.minimum(xi - 1.0, -1e-12))
            num = mu * ri
            den = mu * mu * (-2.0 * ax[i] + pi)   # mu^2 * pi(s_i - x), a_ii=0
            eps = jnp.where(den < 0.0, jnp.minimum(-num / den, 1.0), 1.0)
            scale = eps * mu
            col = affinity_column_ref(v32, i, k32, p)
            col = jnp.where(idx == idx[i], 0.0, col)
            col = jnp.where(mask, col, 0.0)
            onehot = jnp.zeros_like(x).at[i].set(1.0)
            x_new = jnp.maximum(x + scale * (onehot - x), 0.0)
            ax_new = ax + scale * (col - ax)
            if refresh_every > 0:
                def refresh(args):
                    x_new, ax_new = args
                    w = jnp.where(mask & (x_new > support_eps), x_new, 0.0)
                    # evaluated on the slot axis padded to the kernel's
                    # 128-lane layout: XLA's CPU dot associates the (cap,
                    # cap) block differently per operand shape, and the pad
                    # slots carry zero weight, so padding only pins the bits
                    pad = (-cap) % 128
                    vp = jnp.pad(v32, ((0, pad), (0, 0)))
                    ip = jnp.pad(idx, (0, pad), constant_values=-1)
                    full = affinity_matvec_ref(vp, ip, vp, ip,
                                               jnp.pad(w, (0, pad)), k32,
                                               p)[:cap]
                    return jnp.where(mask, full, 0.0)
                hit = (it + 1) % refresh_every == 0
                ax_new = jax.lax.cond(hit, refresh, lambda a: a[1],
                                      (x_new, ax_new))
            return x_new, ax_new

        x, ax = jax.lax.cond(done, lambda a: a, update, (x, ax))
        return t + 1, x, ax, it + 1, done

    def cond(carry):
        t, _, _, it, cv = carry
        return (t < n_steps) & (~cv) & (it < max_iters)

    _, x, ax, it, cv = jax.lax.while_loop(
        cond, step,
        (jnp.int32(0), x.astype(jnp.float32), ax.astype(jnp.float32),
         jnp.asarray(n_iters, jnp.int32), jnp.asarray(converged, bool)))
    return x, ax, it, cv


def assign_ref(q: jax.Array, sup_v: jax.Array, sup_w: jax.Array,
               dens: jax.Array, k_scale: jax.Array,
               threshold: jax.Array, bm: int = 512
               ) -> tuple[jax.Array, jax.Array]:
    """Fused batched cluster assignment (Clustering.predict / ClusterServer):
    affinity against every cluster support + weighted score + argmax +
    density-threshold accept, one pass.

    q:(m,d), sup_v:(C,A,d), sup_w:(C,A), dens:(C,), threshold:() ->
    (labels (m,) int32 with -1 = no cluster, best_score (m,) f32).

    The per-cluster weighted score is a segment reduce (einsum over the A
    axis), and queries process in `bm`-row chunks mirroring the Pallas
    grid, so the (bm, C*A) affinity block stays cache-resident instead of a
    whole (m, C*A) round-trip.
    """
    n_clusters, a_cap, d = sup_v.shape
    sup_flat = sup_v.reshape(n_clusters * a_cap, d)

    def block(qb):
        aff = affinity_ref(qb, sup_flat, k_scale).astype(jnp.float32)
        scores = jnp.einsum(
            "mca,ca->mc", aff.reshape(-1, n_clusters, a_cap), sup_w,
            precision=HIGHEST)
        best = jnp.argmax(scores, axis=-1).astype(jnp.int32)
        bscore = jnp.max(scores, axis=-1)
        ok = bscore >= threshold * dens[best]
        return jnp.where(ok, best, -1).astype(jnp.int32), bscore

    m = q.shape[0]
    if m <= bm:
        return block(q)
    pm = (-m) % bm
    qp = jnp.pad(q, ((0, pm), (0, 0)))          # pad labels sliced off below
    labels, bscore = jax.lax.map(block, qp.reshape(-1, bm, q.shape[1]))
    return labels.reshape(-1)[:m], bscore.reshape(-1)[:m]


# --------------------------------------------------------- flash attention --
def _pad_mask(q_offset, kv_start, sq, sk):
    """Per-row position mask for LEFT-PADDED serving batches.

    kv_start:(B,) = number of pad slots at the front of each row's kv
    timeline. Returns (qpos, kpos, mask) in LOGICAL positions (slot -
    kv_start) with pad kv slots masked out: causal masking is shift-
    invariant, but window/chunk masks are not, so they must see logical
    positions for a packed short prompt to match its solo run.
    """
    start = jnp.asarray(kv_start, jnp.int32)[:, None, None]       # (B,1,1)
    qpos = (jnp.asarray(q_offset) + jnp.arange(sq))[None, :, None] - start
    kpos = jnp.arange(sk)[None, None, :] - start                  # (B,1,Sk)
    return qpos, kpos, kpos >= 0


def _attention_dense(q, k, v, *, causal, window, chunk, softcap, q_offset,
                     scale, flat_gqa=True, kv_start=None):
    """One dense block: q (B,H,Sq,dh) vs full kv. Sq is a q-block.

    GQA is handled by REPEATING kv to flat heads rather than reshaping q to
    (groups, rep): a (64)-way head dim sharded over a 16-way model axis
    cannot re-factor into (8 groups, 8 reps) without SPMD 'involuntary full
    rematerialization' (measured: 4.2 TB/step of f32 gathers on kimi-k2).
    The repeat broadcast SHARDS the head dim cleanly; same FLOPs."""
    b, h, sq, dh = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = h // hkv
    if rep > 1 and sq > 1 and flat_gqa:
        # flat heads for training/prefill shapes (see docstring); decode
        # (sq==1) keeps grouped kv — repeating the kv cache there quadruples
        # transient memory for zero collective win (measured on danube/gemma2
        # decode_32k).
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    elif rep > 1:
        out = _attention_grouped(q, k, v, causal=causal, window=window,
                                 chunk=chunk, softcap=softcap,
                                 q_offset=q_offset, scale=scale,
                                 kv_start=kv_start)
        return out

    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)

    if kv_start is None:
        qpos = jnp.asarray(q_offset) + jnp.arange(sq)[:, None]  # (Sq, 1)
        kpos = jnp.arange(sk)[None, :]                          # (1, Sk)
        mask = jnp.ones((sq, sk), bool)
    else:
        qpos, kpos, mask = _pad_mask(q_offset, kv_start, sq, sk)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    if chunk is not None:
        mask = mask & ((kpos // chunk) == (qpos // chunk))
    mask = mask[None, None] if kv_start is None else mask[:, None]
    logits = jnp.where(mask, logits, MASK_VALUE)

    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _attention_grouped(q, k, v, *, causal, window, chunk, softcap, q_offset,
                       scale, kv_start=None):
    """Grouped-GQA einsum (kv kept at Hkv heads) — decode path."""
    b, h, sq, dh = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = h // hkv
    qr = q.reshape(b, hkv, rep, sq, dh).astype(jnp.float32)
    logits = jnp.einsum("bgrqd,bgkd->bgrqk", qr, k.astype(jnp.float32)) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    if kv_start is None:
        qpos = jnp.asarray(q_offset) + jnp.arange(sq)[:, None]
        kpos = jnp.arange(sk)[None, :]
        mask = jnp.ones((sq, sk), bool)
    else:
        qpos, kpos, mask = _pad_mask(q_offset, kv_start, sq, sk)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    if chunk is not None:
        mask = mask & ((kpos // chunk) == (qpos // chunk))
    mask = (mask[None, None, None] if kv_start is None
            else mask[:, None, None])
    logits = jnp.where(mask, logits, MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrqk,bgkd->bgrqd", probs, v.astype(jnp.float32))
    return out.reshape(b, h, sq, dh).astype(q.dtype)


def attention_ref(
    q: jax.Array,               # (B, H, Sq, dh)
    k: jax.Array,               # (B, Hkv, Sk, dh)
    v: jax.Array,               # (B, Hkv, Sk, dh)
    *,
    causal: bool = True,
    window: int | None = None,  # sliding-window size (tokens attended back)
    chunk: int | None = None,   # chunked/local attention (llama4-style)
    softcap: float | None = None,
    q_offset: jax.Array | int = 0,  # position of q[0] on the kv timeline
    scale: float | None = None,
    block_q: int = 1024,
    flat_gqa: bool = True,   # False: grouped kv einsum (heads % mesh != 0)
    kv_start: jax.Array | None = None,  # (B,) left-pad slots per row
) -> jax.Array:
    """XLA-path attention with flash-like memory behaviour: long sequences are
    scanned in q blocks (each checkpointed), so live probs are (B,H,bq,Sk)
    instead of (B,H,Sq,Sk) — this is what the dry-run lowers and what the
    per-device memory_analysis reflects.

    `kv_start` is the left-padded-batch contract (serve.BatchServer): row i's
    kv slots [0, kv_start[i]) are padding — never attended — and position
    masks shift to logical positions slot - kv_start[i], so a short prompt
    packed next to a longer one sees exactly the attention pattern of its
    solo run. None = no padding (the training / single-sequence path,
    bit-identical to before)."""
    b, h, sq, dh = q.shape
    scale = (dh ** -0.5) if scale is None else scale
    kw = dict(causal=causal, window=window, chunk=chunk, softcap=softcap,
              scale=scale, flat_gqa=flat_gqa, kv_start=kv_start)
    if sq <= block_q or sq % block_q != 0:
        return _attention_dense(q, k, v, q_offset=q_offset, **kw)

    n_blk = sq // block_q
    qb = jnp.moveaxis(q.reshape(b, h, n_blk, block_q, dh), 2, 0)
    offs = jnp.asarray(q_offset) + jnp.arange(n_blk) * block_q

    @jax.checkpoint
    def one(carry, args):
        qi, oi = args
        return carry, _attention_dense(qi, k, v, q_offset=oi, **kw)

    from repro.models.flags import scan_unroll
    _, out = jax.lax.scan(one, 0, (qb, offs),
                          unroll=scan_unroll(n_blk))  # (n_blk, B, H, bq, dh)
    return jnp.moveaxis(out, 0, 2).reshape(b, h, sq, dh)


# ------------------------------------------------------------ segment sum  --
def segment_matmul_ref(msg: jax.Array, seg_ids: jax.Array, n_segments: int) -> jax.Array:
    """sum_e msg[e] into out[seg_ids[e]] — the GNN aggregation primitive.
    Negative seg_ids are dropped (padding)."""
    valid = seg_ids >= 0
    safe = jnp.where(valid, seg_ids, 0)
    contrib = jnp.where(valid[:, None], msg.astype(jnp.float32), 0.0)
    out = jax.ops.segment_sum(contrib, safe, num_segments=n_segments)
    return out.astype(msg.dtype)


# ----------------------------------------------------------- embedding bag --
def embedding_bag_ref(table: jax.Array, idx: jax.Array, bag_ids: jax.Array,
                      n_bags: int, mode: str = "sum") -> jax.Array:
    """Gather table rows by idx and segment-reduce into bags. idx < 0 = pad."""
    valid = idx >= 0
    rows = table[jnp.where(valid, idx, 0)].astype(jnp.float32)
    rows = jnp.where(valid[:, None], rows, 0.0)
    safe_bags = jnp.where(valid, bag_ids, 0)
    out = jax.ops.segment_sum(rows, safe_bags, num_segments=n_bags)
    if mode == "mean":
        cnt = jax.ops.segment_sum(valid.astype(jnp.float32), safe_bags,
                                  num_segments=n_bags)
        out = out / jnp.maximum(cnt, 1.0)[:, None]
    return out.astype(table.dtype)


# --------------------------------------------------------------- lsh hash  --
def lsh_hash_ref(x: jax.Array, proj: jax.Array, bias: jax.Array,
                 seg_len: float) -> jax.Array:
    """x:(n,d), proj:(L,m,d), bias:(L,m) -> int32 keys (n, L) (the kernels
    produce int32; callers bitcast to uint32)."""
    z = jnp.einsum("nd,lmd->nlm", x.astype(jnp.float32),
                   proj.astype(jnp.float32), precision=HIGHEST)
    z = z + bias[None].astype(jnp.float32)
    h = jnp.floor(z / seg_len).astype(jnp.int32)
    acc = jnp.full(h.shape[:-1], jnp.uint32(0x811C9DC5))
    hu = h.astype(jnp.uint32)
    mul = jnp.uint32(0x9E3779B1)
    for j in range(h.shape[-1]):
        acc = (acc ^ hu[..., j]) * mul
        acc = acc ^ (acc >> jnp.uint32(15))
    return jax.lax.bitcast_convert_type(acc, jnp.int32)
