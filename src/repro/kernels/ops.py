"""Public jit'd wrappers for the kernel layer — THE compute backend of the
whole system. Every hot-path consumer (`core.affinity`, `core.lid`,
`core.civs`, `core.roi`, `lsh.pstable`, `serve`) calls these wrappers; none
of them owns a private affinity / distance / hashing implementation.

Dispatch policy — every op takes `backend`:

  "auto"      resolve from the environment: REPRO_KERNEL_BACKEND if set,
              else interpret when REPRO_KERNEL_INTERPRET=1 (kernel test
              suite / debugging), else "pallas" when JAX's default backend
              is a TPU and "ref" on any other (the CPU test suite; the refs
              are also what the multi-pod dry-run lowers). A caller that
              must run the compiled kernels checks the resolved mode
              itself: `chip_smoke.py` refuses anything but "pallas".
  "ref"       the pure-jnp oracles in `repro.kernels.ref`.
  "pallas"    the compiled Pallas TPU kernels.
  "interpret" the Pallas kernels in interpreter mode — same kernel code,
              executed as jax ops, so it jits and runs anywhere. The
              engine-parity suite runs fits under interpret vs ref and
              asserts bit-identical labels.

The knob is plumbed as `EngineSpec(backend=...)` through ALIDConfig, all
four engines, store/pipeline builds, ClusterService, and
`run_palid --backend`; "auto" stays the default everywhere, so the env-var
override keeps working for code that never threads a spec.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.affinity import affinity_pallas
from repro.kernels.affinity_matvec import affinity_matvec_pallas
from repro.kernels.assign import assign_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lid_sweep import lid_sweep_pallas
from repro.kernels.lsh_hash import lsh_hash_pallas
from repro.kernels.roi_filter import roi_filter_pallas
from repro.kernels.segment_matmul import segment_matmul_pallas

BACKENDS = ("auto", "ref", "pallas", "interpret")
DTYPES = ("float32", "bfloat16")


def storage_dtype(name: str):
    """Map the `EngineSpec.dtype` knob to the jnp STORAGE dtype (validated).

    Part of the kernel layer's mixed-precision contract: points / store
    shards / v_beta support blocks are stored in this dtype, while every
    distance, affinity, and LID accumulator (x, ax, pi) stays f32 — each op
    upcasts storage inputs exactly once at entry. All engine/store builds
    route their point casts through this helper so the bf16 rounding happens
    once, BEFORE hashing (LSH keys of the rounded values are then identical
    across replicated / sharded / streamed builds)."""
    if name not in DTYPES:
        raise ValueError(
            f"unknown storage dtype {name!r}; expected one of {DTYPES}")
    return jnp.bfloat16 if name == "bfloat16" else jnp.float32


def resolve_backend(backend: str = "auto") -> str:
    """Collapse a backend knob to a concrete mode ("ref"/"pallas"/
    "interpret"). The ONE dispatch decision — every op routes through it."""
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {backend!r}; expected one of {BACKENDS}")
    if backend != "auto":
        return backend
    env = os.environ.get("REPRO_KERNEL_BACKEND", "")
    if env:
        if env not in BACKENDS or env == "auto":
            raise ValueError(
                f"REPRO_KERNEL_BACKEND={env!r}; expected ref|pallas|interpret")
        return env
    if os.environ.get("REPRO_KERNEL_INTERPRET") == "1":
        return "interpret"
    return "pallas" if jax.default_backend() == "tpu" else "ref"


# kept for back-compat with older call sites/tests
def _mode() -> str:
    return resolve_backend("auto")


def affinity(q: jax.Array, c: jax.Array, k_scale, p: float = 2.0, *,
             backend: str = "auto", **kw) -> jax.Array:
    """exp(-k ||q_i - c_j||_p): (m, d), (n, d) -> (m, n), no diagonal logic.
    The Pallas kernel implements p=2 (the paper's metric, all experiments);
    other norms run the shared jnp reference on every backend."""
    mode = resolve_backend(backend)
    if mode == "ref" or p != 2.0:
        return _ref.affinity_ref(q, c, jnp.asarray(k_scale, jnp.float32), p)
    return affinity_pallas(q, c, jnp.asarray(k_scale, jnp.float32),
                           interpret=(mode == "interpret"), **kw)


def pairwise_distance(q: jax.Array, c: jax.Array, p: float = 2.0, *,
                      backend: str = "auto") -> jax.Array:
    """||q_i - c_j||_p in f32 — the ONE distance contraction (see
    `ref.pairwise_distance_ref`). No standalone Pallas kernel: every
    hot-path distance is fused into affinity / roi_filter / assign, and the
    remaining callers (estimate_k, shard-routing metadata) are per-build
    metadata passes; `backend` is validated for signature uniformity."""
    resolve_backend(backend)
    return _ref.pairwise_distance_ref(q, c, p)


def affinity_matvec(q: jax.Array, q_idx: jax.Array, c: jax.Array,
                    c_idx: jax.Array, w: jax.Array, k_scale,
                    p: float = 2.0, *, backend: str = "auto",
                    **kw) -> jax.Array:
    """Masked affinity x weights matvec (Ax refresh, Eq. 13/17):
    out_i = sum_j [q_idx_i != c_idx_j] exp(-k||q_i - c_j||) w_j, (m,) f32.
    Slot-validity masks fold into `w` (c side) / an output row select
    (q side) — exact, and the (m, n) block never hits HBM on the kernel
    path."""
    mode = resolve_backend(backend)
    if mode == "ref" or p != 2.0:
        return _ref.affinity_matvec_ref(q, q_idx, c, c_idx, w,
                                        jnp.asarray(k_scale, jnp.float32), p)
    return affinity_matvec_pallas(q, q_idx, c, c_idx, w,
                                  jnp.asarray(k_scale, jnp.float32),
                                  interpret=(mode == "interpret"), **kw)


def lid_sweep(v_beta: jax.Array, beta_idx: jax.Array, beta_mask: jax.Array,
              x: jax.Array, ax: jax.Array, n_iters: jax.Array,
              converged: jax.Array, k_scale, *, n_steps: int, max_iters: int,
              tol: float, p: float = 2.0, refresh_every: int = 0,
              support_eps: float = 1e-6, backend: str = "auto",
              **kw) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused multi-iteration LID sweep (Sec. 4.1, Eq. 9-14): up to `n_steps`
    infection-immunization iterations over one (cap, d) support block
    entirely in VMEM — on-demand affinity column, residual/argmax, invasion
    share, x/Ax update per step, gated on the early-exit flag.

    (x, ax, n_iters, converged) in, same out; `n_iters` is CUMULATIVE (the
    step guard is `~converged & (n_iters < max_iters)`), so `lid_solve`'s
    while-over-chunks composition is bit-identical to the historical
    single-step while_loop on the ref backend. `v_beta` may be bf16 storage;
    x/ax/pi accumulate in f32 on every backend. `refresh_every=M > 0` adds
    an exact in-sweep Ax recompute (masked matvec) every M iterations —
    off by default to preserve the incremental-update bit contract.
    Batched seeds: vmap — the kernel path batches onto a leading grid dim.
    """
    mode = resolve_backend(backend)
    if mode == "ref" or p != 2.0:
        return _ref.lid_sweep_ref(v_beta, beta_idx, beta_mask, x, ax,
                                  n_iters, converged,
                                  jnp.asarray(k_scale, jnp.float32),
                                  n_steps, max_iters, tol, p,
                                  refresh_every, support_eps)
    return lid_sweep_pallas(v_beta, beta_idx, beta_mask, x, ax, n_iters,
                            converged, jnp.asarray(k_scale, jnp.float32),
                            n_steps=n_steps, max_iters=max_iters, tol=tol,
                            refresh_every=refresh_every,
                            support_eps=support_eps,
                            interpret=(mode == "interpret"), **kw)


def roi_filter(vc: jax.Array, center: jax.Array, radius, valid: jax.Array,
               p: float = 2.0, *, backend: str = "auto", **kw):
    """Fused CIVS ROI filter: (dist (C,), valid_out (C,) bool, neg (C,))
    with valid_out = valid & (dist <= radius), neg = -dist else -inf (the
    score top-delta selection ranks). One pass over the candidate tile."""
    mode = resolve_backend(backend)
    if p != 2.0:
        dist = _ref.pairwise_distance_ref(vc, center[None, :], p)[:, 0]
        ok = valid & (dist <= radius)
        return dist, ok, jnp.where(ok, -dist, -jnp.inf)
    if mode == "ref":
        return _ref.roi_filter_ref(vc, center, jnp.asarray(radius,
                                                           jnp.float32), valid)
    return roi_filter_pallas(vc, center, jnp.asarray(radius, jnp.float32),
                             valid, interpret=(mode == "interpret"), **kw)


def assign_clusters(q: jax.Array, sup_v: jax.Array, sup_w: jax.Array,
                    dens: jax.Array, k_scale, threshold,
                    valid: jax.Array | None = None, *,
                    backend: str = "auto", **kw):
    """Fused batched cluster assignment (predict / serve): weighted support
    affinity scores + argmax + density-threshold accept.

    q:(m,d), sup_v:(C,A,d), sup_w:(C,A), dens:(C,) ->
    (labels (m,) int32 with -1 = no cluster, best_score (m,) f32).

    `valid` is the slot-validity mask of a padded serving batch ((m,) bool;
    None = every row is a real query). Like the other fused ops it folds
    into the epilogue, not a kernel branch: invalid rows get label -1 and
    score 0 EXACTLY, valid rows are untouched, so a packed batch stays
    bit-identical to per-query assignment on every backend. Pad rows of a
    fixed-slot batch are zero vectors — without the mask they would be real
    points at the origin, scored against every support (and mis-assigned if
    a cluster sits near the origin).
    """
    sup_v = jnp.asarray(sup_v, jnp.float32)
    sup_w = jnp.asarray(sup_w, jnp.float32)
    dens = jnp.asarray(dens, jnp.float32)
    k_scale = jnp.asarray(k_scale, jnp.float32)
    threshold = jnp.asarray(threshold, jnp.float32)
    mode = resolve_backend(backend)
    if mode == "ref":
        labels, score = _ref.assign_ref(q, sup_v, sup_w, dens, k_scale,
                                        threshold)
    else:
        labels, score = assign_pallas(q, sup_v, sup_w, dens, k_scale,
                                      threshold,
                                      interpret=(mode == "interpret"), **kw)
    if valid is not None:
        valid = jnp.asarray(valid, bool)
        labels = jnp.where(valid, labels, -1)
        score = jnp.where(valid, score, 0.0)
    return labels, score


def flash_attention(q, k, v, q_offset=0, *, causal=True, window=None,
                    chunk=None, softcap=None, scale=None, flat_gqa=True,
                    kv_start=None, backend: str = "auto", **kw) -> jax.Array:
    """`kv_start` ((B,) int32 or None) is the left-padded serving-batch
    contract: kv slots < kv_start[b] are pad — never attended — and the
    causal/window/chunk masks run in logical positions (slot - kv_start), so
    packed prompts match their solo runs. None = no padding."""
    mode = resolve_backend(backend)
    if mode == "ref":
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  chunk=chunk, softcap=softcap,
                                  q_offset=q_offset, scale=scale,
                                  flat_gqa=flat_gqa, kv_start=kv_start)
    return flash_attention_pallas(q, k, v, q_offset, causal=causal,
                                  window=window, chunk=chunk, softcap=softcap,
                                  scale=scale, interpret=(mode == "interpret"),
                                  kv_start=kv_start, **kw)


def segment_matmul(msg, seg_ids, n_segments: int, *, backend: str = "auto",
                   **kw) -> jax.Array:
    mode = resolve_backend(backend)
    if mode == "ref":
        return _ref.segment_matmul_ref(msg, seg_ids, n_segments)
    out = segment_matmul_pallas(msg, seg_ids, n_segments,
                                interpret=(mode == "interpret"), **kw)
    # zero rows whose whole row-block was never visited (no edges)
    bw = kw.get("bw", 128)
    rb = jnp.where(seg_ids >= 0, seg_ids // bw, n_segments // bw + 1)
    visited = jnp.zeros(((n_segments + bw - 1) // bw + 2,), bool).at[rb].set(True)
    return jnp.where(visited[jnp.arange(n_segments) // bw][:, None], out, 0.0)


def embedding_bag(table, idx, bag_ids, n_bags: int, mode: str = "sum", *,
                  backend: str = "auto", **kw):
    kmode = resolve_backend(backend)
    if kmode == "ref" or mode == "mean":
        out = _ref.embedding_bag_ref(table, idx, bag_ids, n_bags, mode=mode)
        return out
    out = embedding_bag_pallas(table, idx, bag_ids, n_bags,
                               interpret=(kmode == "interpret"), **kw)
    bw = kw.get("bw", 128)
    rb = jnp.where(bag_ids >= 0, bag_ids // bw, n_bags // bw + 1)
    visited = jnp.zeros(((n_bags + bw - 1) // bw + 2,), bool).at[rb].set(True)
    return jnp.where(visited[jnp.arange(n_bags) // bw][:, None], out, 0.0)


def lsh_hash(x, proj, bias, seg_len: float, *, backend: str = "auto",
             **kw) -> jax.Array:
    """p-stable bucket keys for x:(n,d) -> (n, L) int32 (callers bitcast to
    uint32). Convention: the projection einsum runs in f32 regardless of the
    input dtype — `pstable.hash_points` and both kernel paths share it, so
    Sharded/Streamed store key identity holds across dtypes."""
    mode = resolve_backend(backend)
    if mode == "ref":
        return _ref.lsh_hash_ref(x, proj, bias, seg_len)
    return lsh_hash_pallas(x, proj, bias, seg_len,
                           interpret=(mode == "interpret"), **kw)
