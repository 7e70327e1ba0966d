"""Pallas TPU kernel for blocked Laplacian-kernel affinity — the paper's hot
spot (every CIVS refresh and LID column is one of these blocks).

Tiling: grid (M/bm, N/bn); each program loads a (bm, d) query tile and a
(bn, d) candidate tile into VMEM, computes ||q-c||^2 via the MXU contraction
-2*q@c^T plus row/col norms (VPU), then the exp(-k*sqrt(.)) epilogue in
registers. bm = bn = 128 aligns both MXU operand dims; d is kept whole per
block (ALID feature dims are <= ~1k, so a 128 x 1024 f32 tile is 512 KiB —
three tiles fit easily in 16 MiB VMEM with double buffering).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _affinity_kernel(k_ref, q_ref, c_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)          # (bm, d)
    c = c_ref[...].astype(jnp.float32)          # (bn, d)
    k_scale = k_ref[0, 0]
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)             # (bm, 1)
    c2 = jnp.sum(c * c, axis=-1, keepdims=True).T           # (1, bn)
    d2 = q2 + c2 - 2.0 * jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    o_ref[...] = jnp.exp(-k_scale * dist).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def affinity_pallas(
    q: jax.Array,
    c: jax.Array,
    k_scale: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    m, d = q.shape
    n = c.shape[0]
    pm, pn = (-m) % bm, (-n) % bn
    qp = jnp.pad(q, ((0, pm), (0, 0)))
    cp = jnp.pad(c, ((0, pn), (0, 0)))
    k_arr = jnp.asarray(k_scale, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        _affinity_kernel,
        grid=((m + pm) // bm, (n + pn) // bn),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), q.dtype),
        interpret=interpret,
    )(k_arr, qp, cp)
    return out[:m, :n]
