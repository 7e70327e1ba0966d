"""Localized Infection-Immunization Dynamics (LID) — paper Sec. 4.1, Alg. 1.

The TPU-native re-design: the dynamic local range beta becomes a FIXED-CAPACITY
buffer (`cap = a_cap + delta`) with a validity mask. Every iteration:

  1. r_i = (A_beta,alpha x_alpha)_i - pi(x)            (Eq. 10)
  2. pick i* = argmax |r| over C1 ∪ C2                 (Eq. 6)
  3. invasion share eps via Eq. 9/11/12
  4. x, Ax updated with ONE on-demand affinity column  (Eq. 13/14)

The on-demand column A[beta, i*] = exp(-k||v_beta - v_i*||) is the only O(b*d)
work per step — this is the paper's "selectively computing a few columns"
insight, realized as one fused distance+exp block (Pallas kernel on TPU).
Everything is shape-static so a batch of seeds runs under vmap in lockstep,
turning the b×d matvecs into MXU matmuls (a beyond-paper optimization:
batched-seed LID).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


class LIDState(NamedTuple):
    beta_idx: jax.Array   # (cap,) int32 global data indices (garbage where ~mask)
    beta_mask: jax.Array  # (cap,) bool
    v_beta: jax.Array     # (cap, d) gathered data items
    x: jax.Array          # (cap,) simplex weights restricted to beta
    ax: jax.Array         # (cap,) (A_beta,alpha x_alpha)
    n_iters: jax.Array    # () int32 cumulative LID iterations
    converged: jax.Array  # () bool


def init_state_from(v_seed: jax.Array, seed_idx: jax.Array, cap: int) -> LIDState:
    """Alg. 2 line 1 from an already-gathered seed row v_seed:(d,) — lets
    out-of-core drivers seed without a global points array."""
    d = v_seed.shape[0]
    beta_idx = jnp.full((cap,), -1, jnp.int32).at[0].set(seed_idx.astype(jnp.int32))
    beta_mask = jnp.zeros((cap,), bool).at[0].set(True)
    v_beta = jnp.zeros((cap, d), v_seed.dtype).at[0].set(v_seed)
    x = jnp.zeros((cap,), jnp.float32).at[0].set(1.0)
    ax = jnp.zeros((cap,), jnp.float32)
    return LIDState(beta_idx, beta_mask, v_beta, x, ax, jnp.int32(0), jnp.array(False))


def init_state(points: jax.Array, seed_idx: jax.Array, cap: int) -> LIDState:
    """Alg. 2 line 1: beta = {seed}, x = s_seed, Ax = a_ii = 0."""
    return init_state_from(points[seed_idx], seed_idx, cap)


@functools.partial(jax.jit, static_argnames=("max_iters", "tol", "p",
                                             "backend", "sweep_steps",
                                             "refresh_every", "support_eps"))
def lid_solve(state: LIDState, k: jax.Array, max_iters: int = 200,
              tol: float = 1e-5, p: float = 2.0, backend: str = "auto",
              sweep_steps: int = 8, refresh_every: int = 0,
              support_eps: float = 1e-6) -> LIDState:
    """Run LID to convergence within the (masked) local range.

    Implemented as a while over `ops.lid_sweep` chunks: each chunk runs up
    to `sweep_steps` fused iterations (one kernel launch on the Pallas
    path), and the outer loop re-checks `~converged & (n_iters < max_iters)`
    between chunks. Because the sweep's per-step guard is the same
    predicate, the executed-iteration sequence — and therefore x/ax/n_iters
    — is bit-identical to the historical single-step while_loop
    (`lid_solve_unfused`) on the ref backend. `sweep_steps <= 0` means one
    full-`max_iters` sweep. `refresh_every=M > 0` opts into the in-sweep
    exact Ax recompute every M iterations (recommended with bf16 storage).
    """
    n_steps = min(sweep_steps, max_iters) if sweep_steps > 0 else max_iters

    def cond(s: LIDState):
        return (~s.converged) & (s.n_iters < max_iters)

    def body(s: LIDState):
        x, ax, it, cv = ops.lid_sweep(
            s.v_beta, s.beta_idx, s.beta_mask, s.x, s.ax, s.n_iters,
            s.converged, k, n_steps=n_steps, max_iters=max_iters, tol=tol,
            p=p, refresh_every=refresh_every, support_eps=support_eps,
            backend=backend)
        return LIDState(s.beta_idx, s.beta_mask, s.v_beta, x, ax, it, cv)

    return jax.lax.while_loop(cond, body,
                              state._replace(converged=jnp.array(False)))


@functools.partial(jax.jit, static_argnames=("max_iters", "tol", "p"))
def lid_solve_unfused(state: LIDState, k: jax.Array, max_iters: int = 200,
                      tol: float = 1e-5, p: float = 2.0) -> LIDState:
    """The pre-sweep reference loop: one XLA-dispatched iteration per
    while_loop step. Kept as the bit-parity oracle for `lid_solve`'s
    chunked sweeps (tests/test_lid_sweep.py) and as the unfused arm of the
    kernel benchmark — not called on any hot path."""

    def cond(s: LIDState):
        return (~s.converged) & (s.n_iters < max_iters)

    def body(s: LIDState):
        pi = ref.tree_sum(s.x * s.ax)
        r = jnp.where(s.beta_mask, s.ax - pi, 0.0)
        c1 = s.beta_mask & (r > tol)
        c2 = s.beta_mask & (r < -tol) & (s.x > 0.0)
        score = jnp.where(c1 | c2, jnp.abs(r), -jnp.inf)
        i = jnp.argmax(score)
        done = score[i] <= tol

        def update(args):
            x, ax = args
            ri = r[i]
            xi = x[i]
            mu = jnp.where(ri > 0.0, 1.0, xi / jnp.minimum(xi - 1.0, -1e-12))
            num = mu * ri
            den = mu * mu * (-2.0 * ax[i] + pi)  # mu^2 * pi(s_i - x), a_ii=0
            eps = jnp.where(den < 0.0, jnp.minimum(-num / den, 1.0), 1.0)
            scale = eps * mu

            col = ref.affinity_column_ref(s.v_beta, i, k, p)
            col = jnp.where(s.beta_idx == s.beta_idx[i], 0.0, col)
            col = jnp.where(s.beta_mask, col, 0.0)

            onehot = jnp.zeros_like(x).at[i].set(1.0)
            x_new = jnp.maximum(x + scale * (onehot - x), 0.0)
            ax_new = ax + scale * (col - ax)
            return x_new, ax_new

        # the converged iteration is O(cap): the affinity column (the only
        # O(cap*d) work) is gated on `done` instead of discarded by a where
        x, ax = jax.lax.cond(done, lambda a: a, update, (s.x, s.ax))
        return LIDState(s.beta_idx, s.beta_mask, s.v_beta, x, ax,
                        s.n_iters + 1, done)

    return jax.lax.while_loop(cond, body,
                              state._replace(converged=jnp.array(False)))


def refresh_ax(state: LIDState, k: jax.Array, p: float = 2.0,
               support_eps: float = 1e-6,
               backend: str = "auto") -> LIDState:
    """Exactly recompute (A_beta,alpha x_alpha) from the support — kills the
    f32 drift of the incremental Eq. 14 updates. O(cap^2 d), used once per
    outer ALID iteration (not per LID step). ONE fused masked-matvec kernel:
    the c-side slot mask folds into the (zeroed) weights, the q-side mask is
    a row select — both exact — so the (cap, cap) affinity block never
    round-trips HBM."""
    w = jnp.where(state.beta_mask & (state.x > support_eps), state.x, 0.0)
    ax = ops.affinity_matvec(state.v_beta, state.beta_idx, state.v_beta,
                             state.beta_idx, w, k, p, backend=backend)
    return state._replace(ax=jnp.where(state.beta_mask, ax, 0.0))


def support_size(state: LIDState, support_eps: float = 1e-6) -> jax.Array:
    return jnp.sum(state.beta_mask & (state.x > support_eps))


def density(state: LIDState) -> jax.Array:
    return jnp.sum(state.x * state.ax)
