"""Pallas TPU flash attention (online-softmax) supporting everything the
assigned LM architectures need in one kernel:

  * causal masking                         (all decoder LMs)
  * sliding-window attention               (h2o-danube, gemma2 local layers)
  * chunked/local attention                (llama4-scout iRoPE local layers)
  * logit soft-capping                     (gemma2)
  * GQA — q heads grouped over kv heads    (all five LMs)
  * q_offset for decode/chunked-prefill    (serve_step)

Tiling: grid (B, H, Sq/bq, Sk/bk) with the kv axis innermost and sequential
('arbitrary'); m/l/acc live in VMEM scratch that persists across the kv steps
(the standard TPU flash schedule). Out is written once on the last kv step.
Block sizes default to 128x128 on the MXU; dh is kept whole (128 for all
assigned archs). Fully-masked blocks are still scheduled — production grids
prune them via the index map; we keep the kernel simple and mask instead
(documented trade-off, the dry-run HLO path uses the XLA reference anyway).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


MASK_VALUE = -1e30
LANES = 128


def _flash_kernel(qoff_ref, ks_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, n_kv: int, sk_valid: int, causal: bool,
                  window: int | None, chunk: int | None,
                  softcap: float | None, scale: float):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # (bq, dh)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    iq = pl.program_id(2)
    # left-pad handling (serving batches): kv slots < start are never
    # attended, and position masks run in LOGICAL positions (slot - start) so
    # window/chunk masks of a packed prompt match its solo run; start == 0
    # (the default) reduces to the original slot-space masking exactly.
    start = ks_ref[pl.program_id(0)]
    qpos = (qoff_ref[0] + iq * bq - start
            + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (kpos < sk_valid) & (kpos >= start)  # kv padding is never attended
    kpos = kpos - start
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    if chunk is not None:
        mask &= (kpos // chunk) == (qpos // chunk)
    s = jnp.where(mask, s, MASK_VALUE)

    m_prev = m_ref[:, 0:1]                            # (bq, 1)
    l_prev = l_ref[:, 0:1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    # rows that are fully masked so far keep m=-inf; exp(-1e30-(-inf)) guards:
    p = jnp.where(m_new <= MASK_VALUE, 0.0, p)
    alpha = jnp.where(m_new <= MASK_VALUE, 1.0, jnp.exp(m_prev - m_new))
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)

    v = v_ref[0, 0].astype(jnp.float32)               # (bk, dh)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_kv - 1)
    def _finish():
        l = l_ref[:, 0:1]
        out = acc_ref[...] / jnp.where(l <= 0.0, 1.0, l)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "chunk", "softcap", "scale",
                     "bq", "bk", "interpret"))
def flash_attention_pallas(
    q: jax.Array,            # (B, H, Sq, dh)
    k: jax.Array,            # (B, Hkv, Sk, dh)
    v: jax.Array,            # (B, Hkv, Sk, dh)
    q_offset: jax.Array | int = 0,
    *,
    causal: bool = True,
    window: int | None = None,
    chunk: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
    kv_start: jax.Array | None = None,   # (B,) left-pad slots per row
) -> jax.Array:
    b, h, sq, dh = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = h // hkv
    scale = (dh ** -0.5) if scale is None else scale

    bq = min(bq, sq)
    bk = min(bk, sk)
    pq, pk = (-sq) % bq, (-sk) % bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    # pad kv with zeros; padded keys are masked out via kpos >= sk below only
    # when causal/window already exclude them; add an explicit guard by
    # folding the valid-length test into the position mask with a huge qpos.
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    n_q, n_kv = (sq + pq) // bq, (sk + pk) // bk

    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    ks = (jnp.zeros((b,), jnp.int32) if kv_start is None
          else jnp.asarray(kv_start, jnp.int32).reshape(b))

    grid = (b, h, n_q, n_kv)
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, n_kv=n_kv, sk_valid=sk, causal=causal,
        window=window, chunk=chunk, softcap=softcap, scale=scale)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bq, dh), lambda bb, hh, ii, jj: (bb, hh, ii, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda bb, hh, ii, jj: (bb, hh // rep, jj, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda bb, hh, ii, jj: (bb, hh // rep, jj, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda bb, hh, ii, jj: (bb, hh, ii, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq + pq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qoff, ks, qp, kp, vp)
    return out[:, :, :sq, :]
