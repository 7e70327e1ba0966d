"""Pallas TPU kernel for segment-sum aggregation (the GNN message-passing /
SpMM hot spot) realized as blocked ONE-HOT MATMULS on the MXU.

TPU adaptation of the CSR scatter-add: scatter is hostile to the VPU, but a
(bw x be) one-hot matrix times a (be x d) message tile is a native MXU
contraction. Edges arrive sorted by destination segment and ALIGNED so that no
edge block crosses an output row-block boundary (ops.align_segments does the
layout, MegaBlocks-style). A scalar-prefetched array maps each edge block to
its output row block; consecutive edge blocks that share a row block
accumulate in place (the output block stays resident in VMEM between
consecutive grid steps with the same index).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _segment_kernel(row_block_ref, first_ref, seg_local_ref, msg_ref, o_ref,
                    *, bw: int, be: int):
    i = pl.program_id(0)

    @pl.when(first_ref[i] == 1)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    seg_local = seg_local_ref[...].reshape(be)            # (be,) row within block
    msg = msg_ref[...].astype(jnp.float32)                # (be, d)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bw, be), 0)
    onehot = (rows == seg_local[None, :]).astype(jnp.float32)  # (bw, be)
    o_ref[...] += jax.lax.dot_general(
        onehot, msg, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def align_segments(seg_ids: jax.Array, n_segments: int, be: int, bw: int):
    """Re-layout sorted seg_ids so no be-sized edge block spans two bw-sized
    output row blocks. Returns (perm, new_len, seg_local, row_block, first)
    where perm scatters original edge e -> aligned slot perm[e] (pad slots get
    seg_local = -1, matching nothing)."""
    e = seg_ids.shape[0]
    n_row_blocks = pl.cdiv(n_segments, bw)
    rb = jnp.where(seg_ids >= 0, seg_ids // bw, n_row_blocks)  # pad -> overflow bin
    counts = jnp.bincount(rb, length=n_row_blocks + 1)[:n_row_blocks]
    padded = ((counts + be - 1) // be) * be
    offsets = jnp.concatenate([jnp.zeros(1, padded.dtype), jnp.cumsum(padded)])[:-1]
    # rank of each edge within its row block (seg_ids sorted => stable rank)
    starts = jnp.searchsorted(rb, jnp.arange(n_row_blocks))
    rank = jnp.arange(e) - starts[jnp.clip(rb, 0, n_row_blocks - 1)]
    slot = jnp.where(seg_ids >= 0, offsets[jnp.clip(rb, 0, n_row_blocks - 1)] + rank, -1)
    new_len = int(((e + be - 1) // be + n_row_blocks) * be)  # static upper bound
    # block -> row block map & first-visit flags
    n_blocks = new_len // be
    block_starts = jnp.arange(n_blocks) * be
    cum = jnp.concatenate([offsets, jnp.array([new_len], offsets.dtype)])
    block_row = jnp.clip(jnp.searchsorted(cum, block_starts, side="right") - 1,
                         0, n_row_blocks - 1).astype(jnp.int32)
    first = jnp.concatenate([
        jnp.ones(1, jnp.int32),
        (block_row[1:] != block_row[:-1]).astype(jnp.int32)])
    return slot, new_len, block_row, first


@functools.partial(jax.jit, static_argnames=("n_segments", "be", "bw", "interpret"))
def segment_matmul_pallas(
    msg: jax.Array,       # (E, d) messages, pre-sorted by seg_ids
    seg_ids: jax.Array,   # (E,) destination segments, ascending; -1 = pad
    n_segments: int,
    *,
    be: int = 256,
    bw: int = 128,
    interpret: bool = False,
) -> jax.Array:
    e, d = msg.shape
    slot, new_len, block_row, first = align_segments(seg_ids, n_segments, be, bw)

    # scatter messages/locals into the aligned layout
    amsg = jnp.zeros((new_len, d), msg.dtype)
    valid = slot >= 0
    amsg = amsg.at[jnp.where(valid, slot, new_len - 1)].add(
        jnp.where(valid[:, None], msg, 0))
    alocal = jnp.full((new_len,), -1, jnp.int32)
    alocal = alocal.at[jnp.where(valid, slot, new_len - 1)].set(
        jnp.where(valid, (seg_ids % bw).astype(jnp.int32), -1))
    alocal = alocal.reshape(new_len // be, be)

    n_row_blocks = pl.cdiv(n_segments, bw)
    grid = (new_len // be,)
    out = pl.pallas_call(
        functools.partial(_segment_kernel, bw=bw, be=be),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, be), lambda i, br, fr: (i, 0)),
                pl.BlockSpec((be, d), lambda i, br, fr: (i, 0)),
            ],
            out_specs=pl.BlockSpec((bw, d), lambda i, br, fr: (br[i], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_row_blocks * bw, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_row, first, alocal, amsg)
    return out[:n_segments].astype(msg.dtype)
