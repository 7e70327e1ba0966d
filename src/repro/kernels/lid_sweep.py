"""Pallas TPU kernel for the fused multi-iteration LID sweep (paper Sec. 4.1).

One program holds ONE seed's whole working set in VMEM — the (cap, d) support
block, the index/mask/x/Ax lanes, and the scalar carry — and runs up to
`n_steps` infection-immunization iterations without touching HBM in between.
Unfused (`lid_solve` before this kernel), every iteration was a separate
XLA dispatch chain: affinity column -> residual/argmax -> eps -> x/Ax update,
each round-tripping the (cap,) state through HBM up to `max_iters=200` times
per seed per round. Here the whole sweep is one kernel launch.

Batched-seed LID maps onto the kernel grid through vmap: `pallas_call` with
no explicit grid batches by PREPENDING a grid dimension, so
`vmap(lid_solve)` (the engines' `_lid_batch`) turns B seeds into a B-program
grid — one seed per program, in lockstep with the host-side while over
sweep chunks.

Precision contract (the bf16/f32 mixed path): `v_beta` is STORAGE dtype
(f32 or bf16) and is upcast to f32 once at kernel entry; the affinity
column, pi, x, and Ax all accumulate in f32. The per-iteration math mirrors
`ref.lid_sweep_ref` op for op (one-hot lane selects replace dynamic gathers
— exact, since x + 0.0 == x), so interpret mode is bit-identical to the ref
oracle on every backend.

Early exit: each fori step is gated on `(~converged) & (n_iters < max_iters)`
via lax.cond, so a converged lane skips the O(cap*d) column work for the
rest of the sweep — the in-kernel equivalent of the while_loop early exit.

TPU layout: every per-slot lane (index, mask, x, Ax, the affinity column)
is a (1, capp) row with the slot axis on LANES, capp = cap rounded up to a
multiple of 128; the wrapper pads the slots (mask 0, so a pad slot never
scores, never carries weight, and adds exact zeros to every sum). The
support block is (capp, d) with d on lanes. Each iteration builds the
affinity column on the VPU, in two O(capp*d) elementwise passes over the
block: an exact select-and-sum extracts row v_i, and a multiply-reduce over
d gives the cross term v . v_i (the ref oracle's reduction, so interpret
mode stays bit-equal to it; no MXU contraction). Scalars are (1, 1) tiles.
VMEM holds the block twice (storage + f32 copy) plus O(capp) rows: about
2.3 MiB at cap = 2160, d = 128 in f32. The optional `refresh_every > 0`
branch adds (capp, capp) f32 temporaries, so it is refused above
`MAX_REFRESH_CAP` slots rather than overflowing VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import tree_matvec, tree_sum

LANE = 128
# largest padded slot count the in-sweep Ax refresh accepts: its (capp, capp)
# f32 affinity block and the tree temporaries must fit the scoped VMEM limit
# (v5e at d = 128 compiles capp = 1536 and runs out at 2176)
MAX_REFRESH_CAP = 1024

_NT = (((1,), (1,)), ((), ()))       # contract the last dims: a @ b.T


def _make_kernel(n_steps: int, max_iters: int, tol: float,
                 refresh_every: int, support_eps: float):
    def kernel(k_ref, v_ref, idx_ref, m_ref, x_ref, ax_ref, it_ref, cv_ref,
               xo_ref, axo_ref, ito_ref, cvo_ref):
        k_scale = k_ref[0, 0]
        v = v_ref[...].astype(jnp.float32)                    # (capp, d)
        idx = idx_ref[...]                                    # (1, capp) i32
        mask = m_ref[...] != 0                                # (1, capp)
        capp = v.shape[0]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, capp), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (capp, 1), 0)
        # hoisted |v|^2 — recomputed per call in the ref oracle, but from the
        # same rows through the same reduction, so the value is identical
        v2c = jnp.sum(v * v, axis=-1, keepdims=True)          # (capp, 1)
        v2 = v2c.T                                            # (1, capp)

        def pick(a, sel):
            # exact one-hot lane select: the sum has ONE non-zero term
            return jnp.sum(jnp.where(sel, a, jnp.zeros_like(a)))

        def step(_, carry):
            x, ax, it, cv = carry

            def run(args):
                x, ax, it, _ = args
                pi = tree_sum(x * ax, keepdims=True)          # (1, 1)
                r = jnp.where(mask, ax - pi, 0.0)
                c1 = mask & (r > tol)
                c2 = mask & (r < -tol) & (x > 0.0)
                score = jnp.where(c1 | c2, jnp.abs(r), -jnp.inf)
                best = jnp.max(score)
                # first lane attaining the max == jnp.argmax's tie rule
                i = jnp.min(jnp.where(score == best, lane, capp))
                sel = lane == i
                done = best <= tol

                def update(args):
                    x, ax = args
                    ri = pick(r, sel)
                    xi = pick(x, sel)
                    axi = pick(ax, sel)
                    i_glob = pick(idx, sel)
                    mu = jnp.where(ri > 0.0, 1.0,
                                   xi / jnp.minimum(xi - 1.0, -1e-12))
                    num = mu * ri
                    den = mu * mu * (-2.0 * axi + pi)
                    eps = jnp.where(den < 0.0,
                                    jnp.minimum(-num / den, 1.0), 1.0)
                    scale = eps * mu
                    onehot = jnp.where(sel, 1.0, 0.0)         # (1, capp)
                    # row i of the block by an exact select-and-sum over
                    # the rows (a NaN in a masked-off row stays out of it)
                    vi = jnp.sum(jnp.where(row == i, v, 0.0), axis=0,
                                 keepdims=True)               # (1, d)
                    # on-demand affinity column (Eq. 13/14), op for op as
                    # ref.affinity_column_ref: the cross term is a row
                    # reduction over d, turned onto the lanes
                    c2v = pick(v2, sel)                       # |v_i|^2
                    cross = jnp.sum(v * vi, axis=-1, keepdims=True).T
                    d2 = v2 + c2v - 2.0 * cross               # (1, capp)
                    col = jnp.exp(-k_scale * jnp.sqrt(jnp.maximum(d2, 0.0)))
                    col = jnp.where(idx == i_glob, 0.0, col)
                    col = jnp.where(mask, col, 0.0)
                    x_new = jnp.maximum(x + scale * (onehot - x), 0.0)
                    ax_new = ax + scale * (col - ax)
                    if refresh_every > 0:
                        def refresh(args):
                            x_new, ax_new = args
                            w = jnp.where(mask & (x_new > support_eps),
                                          x_new, 0.0)
                            a = v2c + v2 - 2.0 * jax.lax.dot_general(
                                v, v, _NT,
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
                            a = jnp.exp(-k_scale * jnp.sqrt(
                                jnp.maximum(a, 0.0)))
                            a = jnp.where(idx.T == idx, 0.0, a)
                            # same order-pinned contraction as the ref
                            # oracle's affinity_matvec_ref refresh; the
                            # (capp,) result goes back to a lane row
                            full = tree_matvec(a, w[0])[:, None].T
                            return jnp.where(mask, full, 0.0)
                        hit = (it + 1) % refresh_every == 0
                        ax_new = jax.lax.cond(hit, refresh, lambda a: a[1],
                                              (x_new, ax_new))
                    return x_new, ax_new

                x, ax = jax.lax.cond(done, lambda a: a, update, (x, ax))
                return x, ax, it + 1, done.astype(jnp.int32)

            live = (cv == 0) & (it < max_iters)
            return jax.lax.cond(live, run, lambda a: a, (x, ax, it, cv))

        x, ax, it, cv = jax.lax.fori_loop(
            0, n_steps, step,
            (x_ref[...], ax_ref[...], it_ref[0, 0], cv_ref[0, 0]))
        xo_ref[...] = x
        axo_ref[...] = ax
        ito_ref[...] = jnp.full((1, 1), it, jnp.int32)
        cvo_ref[...] = jnp.full((1, 1), cv, jnp.int32)

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "n_steps", "max_iters", "tol", "refresh_every", "support_eps",
    "interpret"))
def lid_sweep_pallas(
    v_beta: jax.Array,     # (cap, d) storage dtype (f32 or bf16)
    beta_idx: jax.Array,   # (cap,) int32 global ids (-1 invalid)
    beta_mask: jax.Array,  # (cap,) bool
    x: jax.Array,          # (cap,) f32 simplex weights
    ax: jax.Array,         # (cap,) f32 (A_beta,alpha x_alpha)
    n_iters: jax.Array,    # () int32 cumulative iterations
    converged: jax.Array,  # () bool
    k_scale: jax.Array,
    *,
    n_steps: int,
    max_iters: int,
    tol: float,
    refresh_every: int = 0,
    support_eps: float = 1e-6,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    cap, _ = v_beta.shape
    pad = (-cap) % LANE
    capp = cap + pad
    if refresh_every > 0 and capp > MAX_REFRESH_CAP:
        raise ValueError(
            f"lid_sweep: refresh_every > 0 needs a ({capp}, {capp}) f32 "
            f"affinity block in VMEM; only cap <= {MAX_REFRESH_CAP} is "
            f"supported on the Pallas path (got cap={cap}). Use "
            "refresh_every=0 or the ref backend.")

    def row(a, dtype, fill=0):
        return jnp.pad(a.astype(dtype), (0, pad),
                       constant_values=fill).reshape(1, capp)

    def tile(a):
        return jnp.asarray(a, jnp.int32).reshape(1, 1)

    xo, axo, ito, cvo = pl.pallas_call(
        _make_kernel(n_steps, max_iters, tol, refresh_every, support_eps),
        out_shape=[
            jax.ShapeDtypeStruct((1, capp), jnp.float32),
            jax.ShapeDtypeStruct((1, capp), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(jnp.asarray(k_scale, jnp.float32).reshape(1, 1),
      jnp.pad(v_beta, ((0, pad), (0, 0))),
      row(beta_idx, jnp.int32, -1), row(beta_mask, jnp.int32),
      row(x, jnp.float32), row(ax, jnp.float32),
      tile(n_iters), tile(converged))
    return xo[0, :cap], axo[0, :cap], ito[0, 0], cvo[0, 0] != 0
