"""Message-passing GNNs covering the four assigned architectures:

  gin-tu            5 layers, d=64, sum aggregator, learnable eps
  graphsage-reddit  2 layers, d=128, mean aggregator (+ real neighbor sampler)
  meshgraphnet      15 layers, d=128, edge+node MLPs (2-layer), residual
  graphcast         encoder-processor(16 x d=512)-decoder, n_vars outputs

Message passing is jax.ops.segment_sum over an edge index (JAX has no sparse
CSR: the scatter IS the system, per the assignment). The Pallas
segment_matmul kernel is the TPU hot-spot artifact for the same contraction.

Graphs arrive as a GraphBatch of (node_feat, edge_src, edge_dst [, edge_feat,
graph_ids]); -1 edges are padding. Distribution: nodes and edges shard over
the data axes; per-layer gathers/scatters become XLA collectives (measured in
the roofline; a shard_map variant is the hillclimb lever).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed.shardings import constrain
from repro.models import layers as L


class GraphBatch(NamedTuple):
    node_feat: jax.Array            # (N, d_in)
    edge_src: jax.Array             # (E,) int32, -1 = pad
    edge_dst: jax.Array             # (E,) int32, -1 = pad
    edge_feat: Optional[jax.Array] = None   # (E, d_edge)
    graph_ids: Optional[jax.Array] = None   # (N,) for batched small graphs
    n_graphs: int = 1


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    name: str
    kind: str                   # gin | sage | mgn | graphcast
    n_layers: int
    d_hidden: int
    d_in: int
    n_out: int
    aggregator: str = "sum"     # sum | mean
    mlp_layers: int = 2
    d_edge_in: int = 4          # raw edge features (mgn/graphcast stub: displacement)
    graph_level: bool = False   # pool to per-graph outputs (molecule shape)
    remat: bool = True          # checkpoint each MP layer (62M-edge graphs)
    dtype: object = jnp.float32


def _aggregate(msg, dst, n_nodes, aggregator, valid):
    msg = jnp.where(valid[:, None], msg, 0.0)
    safe = jnp.where(valid, dst, 0)
    out = jax.ops.segment_sum(msg, safe, num_segments=n_nodes)
    if aggregator == "mean":
        cnt = jax.ops.segment_sum(valid.astype(msg.dtype), safe,
                                  num_segments=n_nodes)
        out = out / jnp.maximum(cnt, 1.0)[:, None]
    return out


def _mesh_axes_for(n: int):
    """All mesh axes that evenly divide n (widest first), or None."""
    from repro.distributed.context import get_mesh_context
    ctx = get_mesh_context()
    if ctx is None:
        return None, None
    full = ctx.data_axes + (ctx.model_axis,)
    for axes in (full, ctx.data_axes):
        size = 1
        for a in axes:
            size *= ctx.mesh.shape[a]
        if n % size == 0 and size > 1:
            return ctx, axes
    return None, None


def sharded_message_pass(h, edge_fn, src, dst, valid, n_nodes, aggregator,
                         edge_feat=None):
    """Explicit-collective message passing (shard_map over the whole mesh):

      1. all_gather node features ONCE per layer (bf16 on the wire)
      2. gather h[src]/h[dst] + edge_fn LOCALLY on the edge shard
      3. partial segment_sum into a full-size accumulator
      4. psum_scatter back to node shards

    vs. the XLA-auto lowering, which gathered f32 node arrays per consumer
    and all-reduced full f32 scatter results (graphcast/ogb hillclimb: 15
    GB/layer -> ~5 GB/layer in bf16, §Perf iteration 7). Falls back to the
    auto path when no mesh/divisibility."""
    ctx, axes = _mesh_axes_for(n_nodes)
    if ctx is None or src.shape[0] % ctx.mesh.shape[axes[0]] != 0:
        msg, e_out = edge_fn(h[src], h[dst], edge_feat)
        return _aggregate(msg, dst, n_nodes, aggregator, valid), e_out

    from jax.sharding import PartitionSpec as P
    flat = axes if len(axes) > 1 else axes[0]

    def body(h_local, src_l, dst_l, valid_l, ef_l):
        h_full = jax.lax.all_gather(h_local, axes, axis=0, tiled=True)
        msg, e_out = edge_fn(h_full[src_l], h_full[dst_l], ef_l)
        msg = jnp.where(valid_l[:, None], msg, 0.0)
        partial = jax.ops.segment_sum(msg, jnp.where(valid_l, dst_l, 0),
                                      num_segments=n_nodes)
        agg = jax.lax.psum_scatter(partial, axes, scatter_dimension=0,
                                   tiled=True)
        if aggregator == "mean":
            cnt = jax.ops.segment_sum(valid_l.astype(msg.dtype),
                                      jnp.where(valid_l, dst_l, 0),
                                      num_segments=n_nodes)
            cnt = jax.lax.psum_scatter(cnt, axes, scatter_dimension=0,
                                       tiled=True)
            agg = agg / jnp.maximum(cnt, 1.0)[:, None]
        return agg, e_out

    ef = edge_feat if edge_feat is not None else jnp.zeros(
        (src.shape[0], 1), h.dtype)
    agg, e_out = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(flat, None), P(flat), P(flat), P(flat), P(flat, None)),
        out_specs=(P(flat, None), P(flat, None)),
        check_vma=False,
    )(h, src, dst, valid, ef)
    return agg, e_out


def _mlp_sizes(cfg: GNNConfig, d_in: int, d_out: int) -> tuple[int, ...]:
    return (d_in,) + (cfg.d_hidden,) * (cfg.mlp_layers - 1) + (d_out,)


def init_params(rng, cfg: GNNConfig) -> dict:
    d = cfg.d_hidden
    ks = iter(jax.random.split(rng, 4 + 4 * cfg.n_layers))
    p: dict = {"encoder": L.mlp_init(next(ks), (cfg.d_in, d, d), cfg.dtype)}
    if cfg.kind in ("mgn", "graphcast"):
        p["edge_encoder"] = L.mlp_init(next(ks), (cfg.d_edge_in, d, d), cfg.dtype)
    layers = []
    for _ in range(cfg.n_layers):
        lp = {}
        if cfg.kind == "gin":
            lp["eps"] = jnp.zeros((), jnp.float32)
            lp["mlp"] = L.mlp_init(next(ks), _mlp_sizes(cfg, d, d), cfg.dtype)
        elif cfg.kind == "sage":
            lp["w_self"] = L.he_init(next(ks), (d, d), cfg.dtype)
            lp["w_nbr"] = L.he_init(next(ks), (d, d), cfg.dtype)
            lp["b"] = jnp.zeros((d,), cfg.dtype)
        else:  # mgn / graphcast processor layer
            lp["edge_mlp"] = L.mlp_init(next(ks), _mlp_sizes(cfg, 3 * d, d), cfg.dtype)
            lp["node_mlp"] = L.mlp_init(next(ks), _mlp_sizes(cfg, 2 * d, d), cfg.dtype)
        layers.append(lp)
    p["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    p["decoder"] = L.mlp_init(next(ks), (d, d, cfg.n_out), cfg.dtype)
    return p


def abstract_params(cfg: GNNConfig):
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def forward(params: dict, cfg: GNNConfig, g: GraphBatch) -> jax.Array:
    n = g.node_feat.shape[0]
    valid = g.edge_src >= 0
    src = jnp.where(valid, g.edge_src, 0)
    dst = jnp.where(valid, g.edge_dst, 0)

    h = L.mlp_apply(params["encoder"], g.node_feat.astype(cfg.dtype))
    h = constrain(h, "nodes", None)
    e = None
    if cfg.kind in ("mgn", "graphcast"):
        ef = g.edge_feat if g.edge_feat is not None else jnp.zeros(
            (g.edge_src.shape[0], cfg.d_edge_in), cfg.dtype)
        e = L.mlp_apply(params["edge_encoder"], ef.astype(cfg.dtype))
        e = constrain(e, "edges", None)

    def layer_body(carry, lp):
        h, e = carry
        if cfg.kind == "gin":
            agg, _ = sharded_message_pass(
                h, lambda hs, hd, ef: (hs, ef), src, dst, valid, n, "sum")
            h = L.mlp_apply(lp["mlp"], (1.0 + lp["eps"]) * h + agg,
                            act=jax.nn.relu, final_act=True)
        elif cfg.kind == "sage":
            agg, _ = sharded_message_pass(
                h, lambda hs, hd, ef: (hs, ef), src, dst, valid, n, "mean")
            h = jax.nn.relu(L.dense(h, lp["w_self"]) + L.dense(agg, lp["w_nbr"])
                            + lp["b"])
            # analysis: allow(private-distance): SAGE l2-normalizes activations row-wise, not a pairwise distance
            h = h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-6)
        else:  # mgn / graphcast
            def edge_fn(hs, hd, ef):
                e_new = ef + L.mlp_apply(lp["edge_mlp"],
                                         jnp.concatenate([ef, hs, hd], -1))
                return e_new, e_new
            agg, e = sharded_message_pass(h, edge_fn, src, dst, valid, n,
                                          cfg.aggregator, edge_feat=e)
            h = h + L.mlp_apply(lp["node_mlp"], jnp.concatenate([h, agg], -1))
        h = constrain(h, "nodes", None)
        if e is not None:
            e = constrain(e, "edges", None)
        return (h, e), None

    from repro.models.flags import scan_unroll
    body = jax.checkpoint(layer_body, prevent_cse=False) if cfg.remat \
        else layer_body
    (h, e), _ = jax.lax.scan(body, (h, e), params["layers"],
                             unroll=scan_unroll(cfg.n_layers))

    out = L.mlp_apply(params["decoder"], h)
    if cfg.graph_level:
        gids = g.graph_ids if g.graph_ids is not None else jnp.zeros((n,), jnp.int32)
        out = jax.ops.segment_sum(out, gids, num_segments=g.n_graphs)
    return out
