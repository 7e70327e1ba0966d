"""Mixture-of-Experts FFN with sort-based token dispatch and explicit
expert-parallel all-to-alls (DeepSpeed/Megatron-MoE dataflow, TPU-native).

Why not GShard one-hot einsum dispatch: with E=384 (kimi-k2) the (tokens, E,
capacity) dispatch tensor is astronomically larger than the useful compute.
Sort-based dispatch is O(T*k log) bookkeeping + two all-to-alls whose bytes
equal the dispatched activations — the right roofline shape.

Dataflow (inside shard_map over (data..., model)):
  1. router on local tokens -> top-k experts + gates
  2. rank tokens within each expert (argsort), drop beyond capacity C
  3. scatter to local dispatch buffer (E, C, D)
  4. all_to_all over the model axis: (E, C, D) -> (E/m, C*m, D)   [EP dispatch]
  5. batched expert FFN (SwiGLU) with the local expert shard
  6. reverse all_to_all, gather back to tokens, weight by gates   [EP combine]

Off-mesh (smoke tests) the same math runs with the full expert set locally and
no collectives.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import get_mesh_context
from repro.models.layers import normal_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                      # per-expert hidden
    n_shared: int = 0              # always-on shared experts (kimi-k2 style)
    capacity_factor: float = 1.25
    router: str = "softmax"        # "softmax" | "sigmoid" (llama4 top-1)
    norm_topk: bool = True         # renormalize top-k gates (deepseek/kimi)
    aux_loss_coef: float = 0.01


def moe_init(rng, cfg: MoEConfig, d_model: int, dtype) -> dict:
    ks = jax.random.split(rng, 5)
    p = {
        "router": normal_init(ks[0], (d_model, cfg.n_experts), jnp.float32),
        "w_gate": normal_init(ks[1], (cfg.n_experts, d_model, cfg.d_ff), dtype),
        "w_up": normal_init(ks[2], (cfg.n_experts, d_model, cfg.d_ff), dtype),
        "w_down": normal_init(ks[3], (cfg.n_experts, cfg.d_ff, d_model), dtype),
    }
    if cfg.n_shared > 0:
        f = cfg.n_shared * cfg.d_ff
        ks2 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": normal_init(ks2[0], (d_model, f), dtype),
            "w_up": normal_init(ks2[1], (d_model, f), dtype),
            "w_down": normal_init(ks2[2], (f, d_model), dtype),
        }
    return p


def _swiglu_experts(params, h):  # h: (E_local, C, D)
    # expert einsums emit bf16: the MXU accumulates fp32 internally on TPU
    # regardless; declaring f32 outputs made every backward collective move
    # f32 expert-grad tensors (2x wire bytes — kimi hillclimb, §Perf)
    g = jnp.einsum("ecd,edf->ecf", h, params["w_gate"].astype(h.dtype),
                   preferred_element_type=h.dtype)
    u = jnp.einsum("ecd,edf->ecf", h, params["w_up"].astype(h.dtype),
                   preferred_element_type=h.dtype)
    a = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * u
    return jnp.einsum("ecf,efd->ecd", a, params["w_down"].astype(h.dtype),
                      preferred_element_type=h.dtype)


def _dispatch_combine(params, cfg: MoEConfig, x, model_axis: Optional[str]):
    """x: (T, D) local tokens. Returns (out (T, D), aux loss scalar)."""
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = int((t * k / e) * cfg.capacity_factor) + 1
    cap = max(8, -(-cap // 8) * 8)  # round up to 8 for lane alignment

    logits = (x.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    if cfg.router == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)                     # (T, k)
    if cfg.norm_topk and cfg.router == "softmax":
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    pe = jnp.mean(jax.nn.softmax(logits, -1), axis=0)
    fe = jnp.mean(
        (jax.nn.one_hot(eidx, e).sum(1) > 0).astype(jnp.float32), axis=0)
    aux = e * jnp.sum(pe * fe) * cfg.aux_loss_coef

    flat_e = eidx.reshape(-1)                                 # (T*k,)
    order = jnp.argsort(flat_e)                               # stable
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e))
    rank_sorted = jnp.arange(t * k) - seg_start[sorted_e]
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    keep = rank < cap
    dst = jnp.where(keep, flat_e * cap + rank, e * cap)       # drop slot at end

    tok_of = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[dst].set(x[tok_of], mode="drop")
    buf = buf[:-1].reshape(e, cap, d)

    if model_axis is not None:
        buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=1,
                                 tiled=True)                  # (E/m, C*m, D)
        h = _swiglu_experts(params, buf)
        h = jax.lax.all_to_all(h, model_axis, split_axis=1, concat_axis=0,
                               tiled=True)                    # (E, C, D)
    else:
        h = _swiglu_experts(params, buf)

    h = jnp.concatenate([h.reshape(e * cap, d),
                         jnp.zeros((1, d), h.dtype)], axis=0)
    vals = h[dst]                                             # (T*k, D), 0 if dropped
    out = jnp.sum(vals.reshape(t, k, d) * gates[..., None].astype(x.dtype), axis=1)
    return out.astype(x.dtype), aux


def _shared_ffn(params, x):
    s = params["shared"]
    g = jax.nn.silu(x @ s["w_gate"].astype(x.dtype))
    u = x @ s["w_up"].astype(x.dtype)
    return ((g * u) @ s["w_down"].astype(x.dtype)).astype(x.dtype)


def moe_apply(params: dict, cfg: MoEConfig, x: jax.Array):
    """x: (B, S, D) -> (out, aux). Dispatch runs under shard_map when a mesh
    context is set (tokens over data axes [+ seq over model when divisible],
    experts over the model axis)."""
    b, s, d = x.shape
    ctx = get_mesh_context()
    if ctx is None:
        out, aux = _dispatch_combine(params, cfg, x.reshape(b * s, d), None)
        out = out.reshape(b, s, d)
    else:
        m = ctx.n_model
        # training shapes shard tokens over (data..., model-on-seq); decode
        # (S < m) replicates tokens over the model axis — correct, m-fold
        # redundant dispatch compute, negligible at decode (see DESIGN.md).
        # batch=1 long-context decode cannot shard over data either ->
        # fully-replicated dispatch (the a2a still distributes experts).
        seq_shard = s % m == 0 and s >= m
        batch_shard = b % ctx.n_data == 0 and b >= ctx.n_data
        tok_spec = P(ctx.data_axes if batch_shard else None,
                     ctx.model_axis if seq_shard else None, None)
        ep_params = {k: params[k] for k in ("router", "w_gate", "w_up", "w_down")}
        ep_specs = {
            "router": P(None, None),
            "w_gate": P(ctx.model_axis, None, None),
            "w_up": P(ctx.model_axis, None, None),
            "w_down": P(ctx.model_axis, None, None),
        }

        def shard_fn(pp, xx):
            bb, ss, dd = xx.shape
            o, aux = _dispatch_combine(pp, cfg, xx.reshape(bb * ss, dd),
                                       ctx.model_axis)
            # aux must be truly replicated (out_specs P()): average over every
            # mesh axis, not just model — data shards see different tokens.
            aux = jax.lax.pmean(aux, ctx.data_axes + (ctx.model_axis,))
            return o.reshape(bb, ss, dd), aux

        out, aux = jax.shard_map(
            shard_fn, mesh=ctx.mesh,
            in_specs=(ep_specs, tok_spec),
            out_specs=(tok_spec, P()),
            check_vma=False,
        )(ep_params, x)
        aux = jnp.mean(aux)

    if "shared" in params:
        out = out + _shared_ffn(params, x)
    return out, aux
