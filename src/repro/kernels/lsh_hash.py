"""Pallas TPU kernel for p-stable LSH hashing: fused projection matmul +
floor-quantize + per-table multiply-xor fold (CIVS throughput path).

Grid over point blocks; the (L*m, d) projection matrix is tiny and replicated
into VMEM for every program. The matmul (bn, d) @ (d, L*m) runs on the MXU;
quantization and the integer mix run on the VPU; one pass, no HBM round-trips
for intermediates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _lsh_kernel(x_ref, proj_ref, bias_ref, o_ref, *, n_tables: int, n_proj: int,
                seg_len: float):
    x = x_ref[...].astype(jnp.float32)                    # (bn, d)
    w = proj_ref[...].astype(jnp.float32)                 # (L*m, d)
    z = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    z = z + bias_ref[...].astype(jnp.float32)             # (bn, L*m)
    h = jnp.floor(z / seg_len).astype(jnp.int32)
    hu = h.astype(jnp.uint32)
    mul = jnp.uint32(0x9E3779B1)
    keys = []
    for l in range(n_tables):
        acc = jnp.full((x.shape[0],), jnp.uint32(0x811C9DC5))
        for j in range(n_proj):
            acc = (acc ^ hu[:, l * n_proj + j]) * mul
            acc = acc ^ (acc >> jnp.uint32(15))
        keys.append(acc)
    out = jnp.stack(keys, axis=1)                         # (bn, L)
    o_ref[...] = jax.lax.bitcast_convert_type(out, jnp.int32)


@functools.partial(jax.jit, static_argnames=("seg_len", "bn", "interpret"))
def lsh_hash_pallas(
    x: jax.Array,          # (n, d)
    proj: jax.Array,       # (L, m, d)
    bias: jax.Array,       # (L, m)
    seg_len: float,
    *,
    bn: int = 256,
    interpret: bool = False,
) -> jax.Array:
    n, d = x.shape
    n_tables, n_proj, _ = proj.shape
    pm = (-n) % bn
    xp = jnp.pad(x, ((0, pm), (0, 0)))
    w = proj.reshape(n_tables * n_proj, d)
    b = bias.reshape(1, n_tables * n_proj)

    out = pl.pallas_call(
        functools.partial(_lsh_kernel, n_tables=n_tables, n_proj=n_proj,
                          seg_len=seg_len),
        grid=((n + pm) // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((n_tables * n_proj, d), lambda i: (0, 0)),
            pl.BlockSpec((1, n_tables * n_proj), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, n_tables), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pm, n_tables), jnp.int32),
        interpret=interpret,
    )(xp, w, b)
    return out[:n]
