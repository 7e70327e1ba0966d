"""Pallas TPU kernel for the masked affinity x weights matvec behind every
Ax refresh (paper Eq. 13/17): `lid.refresh_ax` and `civs.rebuild_support`
recompute (A_beta,alpha x_alpha) from the support each outer iteration.

Unfused, that is an exp(-k*dist) block materialized to HBM, two mask
multiplies, and a matvec — an O(m*n) f32 round-trip per refresh. Here the
distance expansion (MXU), the exp epilogue, the index-compare diagonal
zeroing, and the weights contraction all happen on one VMEM-resident tile:
the (bm, n) affinity block never leaves the core.

Tiling: grid (M/bm,); each program holds a (bm, d) query tile plus the WHOLE
candidate side (n, d) + (n,) weights in VMEM — n is the LID support capacity
(a_cap or a_cap+delta, a few hundred), so even d ~ 1k keeps the candidate
tile under ~1 MiB. Validity masks are the caller's job: fold the c-side mask
into `w` (zero weight = no contribution, exactly) and select on output rows
for the q side — both are exact because x + 0.0 == x in f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import tree_matvec


def _matvec_kernel(k_ref, q_ref, qi_ref, c_ref, ci_ref, w_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)            # (bm, d)
    c = c_ref[...].astype(jnp.float32)            # (n, d)
    k_scale = k_ref[0, 0]
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)               # (bm, 1)
    c2 = jnp.sum(c * c, axis=-1, keepdims=True).T             # (1, n)
    d2 = q2 + c2 - 2.0 * jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    a = jnp.exp(-k_scale * jnp.sqrt(jnp.maximum(d2, 0.0)))
    a = jnp.where(qi_ref[...] == ci_ref[...], 0.0, a)         # (bm,1)==(1,n)
    # the weights contraction is the ONE stage of this op whose bits reach
    # continuous results (densities via the Ax refresh), so it uses the
    # order-pinned tree_matvec the ref oracle also uses: a lax.dot_general
    # here is reassociated differently by XLA depending on batching context
    # (standalone gemv vs vmapped batched gemm), which broke ref-vs-interpret
    # engine parity by 1 ulp
    o_ref[...] = tree_matvec(a, w_ref[...][:, 0])[:, None]    # (bm, 1)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def affinity_matvec_pallas(
    q: jax.Array,        # (m, d)
    q_idx: jax.Array,    # (m,) int32
    c: jax.Array,        # (n, d)
    c_idx: jax.Array,    # (n,) int32
    w: jax.Array,        # (n,) f32
    k_scale: jax.Array,
    *,
    bm: int = 128,
    interpret: bool = False,
) -> jax.Array:
    m, d = q.shape
    n = c.shape[0]
    pm = (-m) % bm
    qp = jnp.pad(q, ((0, pm), (0, 0)))
    # padded q rows get idx -2: never equal to any real c_idx (>= -1), and
    # their output rows are sliced off anyway
    qip = jnp.pad(q_idx.astype(jnp.int32), (0, pm),
                  constant_values=-2).reshape(-1, 1)
    k_arr = jnp.asarray(k_scale, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        _matvec_kernel,
        grid=((m + pm) // bm,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((n, d), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m + pm, 1), jnp.float32),
        interpret=interpret,
    )(k_arr, qp, qip, c, c_idx.astype(jnp.int32).reshape(1, -1),
      w.astype(jnp.float32).reshape(-1, 1))
    return out[:m, 0]
