"""Pallas TPU kernel for fused batched cluster assignment — the serving hot
path (`Clustering.predict` / `serve.ClusterServer`): affinity of a query
batch against every stored cluster support, the weighted per-cluster score,
the argmax, and the density-threshold accept, in one pass.

Tiling: grid (M/bm, C). Each program holds a (bm, d) query tile and ONE
cluster's (A, d) support tile plus its (1, A) weights, so VMEM is
O(bm·A + A·d) whatever the number of clusters. The cluster axis is the
inner, sequential grid axis: the running best score, its cluster id and
that cluster's density live in VMEM scratch across it, and the last step
applies the density-threshold accept and writes the labels. A strictly
greater score replaces the carry, so ties keep the lowest cluster id —
`jnp.argmax`'s rule, which the ref oracle uses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))       # contract the last dims: a @ b.T


def _assign_kernel(k_ref, t_ref, dn_ref, q_ref, s_ref, w_ref, lab_ref, bs_ref,
                   best_ref, arg_ref, dens_ref):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        best_ref[...] = jnp.full(best_ref.shape, -jnp.inf, jnp.float32)
        arg_ref[...] = jnp.zeros(arg_ref.shape, jnp.int32)
        dens_ref[...] = jnp.zeros(dens_ref.shape, jnp.float32)

    q = q_ref[...].astype(jnp.float32)            # (bm, d)
    s = s_ref[...].astype(jnp.float32)            # (A, d)
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)                  # (bm, 1)
    s2 = jax.lax.dot_general(
        jnp.ones((1, s.shape[1]), jnp.float32), s * s, _NT,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                      # (1, A)
    d2 = q2 + s2 - 2.0 * jax.lax.dot_general(
        q, s, _NT, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                      # (bm, A)
    aff = jnp.exp(-k_ref[0] * jnp.sqrt(jnp.maximum(d2, 0.0)))
    score = jnp.sum(aff * w_ref[...], axis=-1, keepdims=True)    # (bm, 1)

    better = score > best_ref[...]
    best_ref[...] = jnp.where(better, score, best_ref[...])
    arg_ref[...] = jnp.where(better, c, arg_ref[...])
    dens_ref[...] = jnp.where(better, dn_ref[c], dens_ref[...])

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        ok = best_ref[...] >= t_ref[0] * dens_ref[...]
        lab_ref[...] = jnp.where(ok, arg_ref[...], -1)
        bs_ref[...] = best_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def assign_pallas(
    q: jax.Array,         # (m, d) queries
    sup_v: jax.Array,     # (C, A, d) cluster supports
    sup_w: jax.Array,     # (C, A) support weights
    dens: jax.Array,      # (C,) cluster densities
    k_scale: jax.Array,
    threshold: jax.Array,
    *,
    bm: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    m, d = q.shape
    n_clusters, a, _ = sup_v.shape
    bm = min(bm, -(-m // 8) * 8)                 # a small batch pads to 8
    pm = (-m) % bm
    qp = jnp.pad(q, ((0, pm), (0, 0)))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    labels, bscore = pl.pallas_call(
        _assign_kernel,
        grid=((m + pm) // bm, n_clusters),
        in_specs=[
            smem, smem, smem,
            pl.BlockSpec((bm, d), lambda i, c: (i, 0)),
            pl.BlockSpec((None, a, d), lambda i, c: (c, 0, 0)),
            pl.BlockSpec((None, 1, a), lambda i, c: (c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, c: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, c: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m + pm, 1), jnp.int32),
            jax.ShapeDtypeStruct((m + pm, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, 1), jnp.float32),
                        pltpu.VMEM((bm, 1), jnp.int32),
                        pltpu.VMEM((bm, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(k_scale, jnp.float32).reshape(1),
      jnp.asarray(threshold, jnp.float32).reshape(1),
      dens.astype(jnp.float32),
      qp, sup_v, sup_w.astype(jnp.float32).reshape(n_clusters, 1, a))
    return labels[:m, 0], bscore[:m, 0]
