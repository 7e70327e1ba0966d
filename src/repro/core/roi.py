"""Region of Interest — paper Sec. 4.2, Eq. 15/16 and Prop. 1.

Double-deck hyperball H(D, R_in, R_out) around the support centroid:
every point strictly inside R_in is guaranteed infective, every point outside
R_out is guaranteed non-infective (triangle inequality on the Laplacian
kernel). The ROI radius grows from R_in to R_out with the shifted logistic
theta(c) = 1 / (1 + e^{4 - c/2}).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops


class ROI(NamedTuple):
    center: jax.Array   # (d,)
    radius: jax.Array   # ()
    r_in: jax.Array     # ()
    r_out: jax.Array    # ()
    pi: jax.Array       # () density pi(x_hat), recomputed exactly


_EXP_CLAMP = 60.0


def theta(c: jax.Array) -> jax.Array:
    return 1.0 / (1.0 + jnp.exp(4.0 - 0.5 * c.astype(jnp.float32)))


def estimate_roi(
    v_beta: jax.Array,
    beta_idx: jax.Array,
    beta_mask: jax.Array,
    x: jax.Array,
    k: jax.Array,
    c: jax.Array,
    r0: float = 0.4,
    p: float = 2.0,
    support_eps: float = 1e-6,
    backend: str = "auto",
) -> ROI:
    w = jnp.where(beta_mask & (x > support_eps), x, 0.0)
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    w = w / wsum

    with jax.default_matmul_precision("highest"):   # f32 on every backend
        center = w @ v_beta                                     # D = sum x_i v_i

    # pi(x_hat) = w^T A w recomputed exactly over the support block (zero
    # diagonal): the inner A w is the fused masked matvec — off-support
    # columns contribute nothing because their w is exactly 0 — and the
    # (cap, cap) block never materializes.
    aw = ops.affinity_matvec(v_beta, beta_idx, v_beta, beta_idx, w, k, p,
                             backend=backend)
    with jax.default_matmul_precision("highest"):
        pi = w @ aw
    pi = jnp.maximum(pi, 1e-12)

    dist = ops.pairwise_distance(v_beta, center[None, :], p,
                                 backend=backend)[:, 0]

    lam_in = jnp.sum(w * jnp.exp(-jnp.minimum(k * dist, _EXP_CLAMP)))
    lam_out = jnp.sum(w * jnp.exp(jnp.minimum(k * dist, _EXP_CLAMP)))
    r_in = jnp.log(jnp.maximum(lam_in / pi, 1e-12)) / k
    r_out = jnp.log(jnp.maximum(lam_out / pi, 1e-12)) / k
    r_in = jnp.maximum(r_in, 0.0)
    r_out = jnp.maximum(r_out, r_in)

    radius = r_in + theta(c) * (r_out - r_in)
    # Alg. 2: the very first iteration has Ax = 0 so the radii are undefined;
    # the paper fixes R = r0 (0.4) for c == 1.
    radius = jnp.where(c <= 1, jnp.asarray(r0, radius.dtype), radius)
    return ROI(center=center, radius=radius, r_in=r_in, r_out=r_out, pi=pi)
