"""Pallas TPU kernel for EmbeddingBag (recsys lookup hot path): gather rows of
a large embedding table and segment-reduce them into bags.

JAX has no native EmbeddingBag; the reference is take + segment_sum. The
kernel keeps the table in HBM/ANY memory and DMAs just the needed rows: for
each block of (bag-sorted) indices it walks the block with a fori_loop of
dynamic row loads, accumulating into a VMEM one-hot staging tile, then lands
the per-bag sums with the same one-hot MXU contraction as segment_matmul.
Indices are bag-sorted and aligned by ops.align_segments, so each index block
touches one bag row-block only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from repro.kernels.segment_matmul import align_segments


def _bag_kernel(bag_block_ref, first_ref, idx_ref, local_ref, table_ref, o_ref,
                gathered_ref, *, be: int, bw: int, dim: int):
    i = pl.program_id(0)

    @pl.when(first_ref[i] == 1)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    idx = idx_ref[...].reshape(be)

    def body(t, _):
        row = idx[t]
        safe = jnp.maximum(row, 0)
        vec = table_ref[pl.ds(safe, 1), :]                        # (1, dim) DMA
        vec = jnp.where(row >= 0, vec, jnp.zeros_like(vec))
        gathered_ref[pl.ds(t, 1), :] = vec.astype(gathered_ref.dtype)
        return ()

    jax.lax.fori_loop(0, be, body, ())

    local = local_ref[...].reshape(be)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bw, be), 0)
    onehot = (rows == local[None, :]).astype(jnp.float32)
    o_ref[...] += jax.lax.dot_general(
        onehot, gathered_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_bags", "be", "bw", "interpret"))
def embedding_bag_pallas(
    table: jax.Array,     # (V, dim)
    idx: jax.Array,       # (N,) table rows, sorted by bag; -1 = pad
    bag_ids: jax.Array,   # (N,) ascending bag ids; -1 = pad
    n_bags: int,
    *,
    be: int = 256,
    bw: int = 128,
    interpret: bool = False,
) -> jax.Array:
    v, dim = table.shape
    slot, new_len, block_row, first = align_segments(bag_ids, n_bags, be, bw)
    valid = slot >= 0
    aidx = jnp.full((new_len,), -1, jnp.int32)
    aidx = aidx.at[jnp.where(valid, slot, new_len - 1)].set(
        jnp.where(valid, idx.astype(jnp.int32), -1))
    alocal = jnp.full((new_len,), -1, jnp.int32)
    alocal = alocal.at[jnp.where(valid, slot, new_len - 1)].set(
        jnp.where(valid, (bag_ids % bw).astype(jnp.int32), -1))

    n_row_blocks = pl.cdiv(n_bags, bw)
    out = pl.pallas_call(
        functools.partial(_bag_kernel, be=be, bw=bw, dim=dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(new_len // be,),
            in_specs=[
                pl.BlockSpec((1, be), lambda i, br, fr: (i, 0)),
                pl.BlockSpec((1, be), lambda i, br, fr: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),      # table stays in HBM
            ],
            out_specs=pl.BlockSpec((bw, dim), lambda i, br, fr: (br[i], 0)),
            scratch_shapes=[pltpu.VMEM((be, dim), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_row_blocks * bw, dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_row, first, aidx.reshape(-1, be), alocal.reshape(-1, be), table)
    return out[:n_bags].astype(table.dtype)
