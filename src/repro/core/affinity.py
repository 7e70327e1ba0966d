"""Laplacian-kernel affinity: a_ij = exp(-k * ||v_i - v_j||_p), zero diagonal.

This is the paper's Eq. (1). Everything in ALID is phrased against this kernel;
the triangle-inequality ROI bounds (Prop. 1) require a *norm*, so p >= 1.

These functions are thin facades over `repro.kernels.ops` — the single
compute backend (ref / Pallas / interpret, selected by the `backend` knob or
the environment). The distance contraction itself exists exactly once, in
`repro.kernels.ref.pairwise_distance_ref`, shared with the CIVS ROI filter
and the Pallas kernels' tile math.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def pairwise_distance(q: jax.Array, c: jax.Array, p: float = 2.0,
                      backend: str = "auto") -> jax.Array:
    """||q_i - c_j||_p for q:(m,d), c:(n,d) -> (m,n) f32 (see
    `kernels.ref.pairwise_distance_ref` — THE distance implementation)."""
    return ops.pairwise_distance(q, c, p, backend=backend)


def affinity_block(q: jax.Array, c: jax.Array, k: float, p: float = 2.0,
                   backend: str = "auto") -> jax.Array:
    """exp(-k * ||q_i - c_j||_p) for blocks, WITHOUT diagonal zeroing."""
    return ops.affinity(q, c, k, p, backend=backend)


def affinity_matrix(v: jax.Array, k: float, p: float = 2.0,
                    backend: str = "auto") -> jax.Array:
    """Full affinity matrix with zero diagonal (baselines only: O(n^2))."""
    a = affinity_block(v, v, k, p, backend)
    return a * (1.0 - jnp.eye(v.shape[0], dtype=a.dtype))


@functools.partial(jax.jit, static_argnames=("sample", "target", "percentile",
                                             "backend"))
def estimate_k(v: jax.Array, sample: int = 512, target: float = 0.95,
               percentile: float = 10.0, backend: str = "auto") -> jax.Array:
    """Pick the Laplacian scale k so that a CLUSTER-SCALE nearest-neighbour
    pair has affinity ~= target. The paper tunes k per data set but never
    states values; the critical property is that intra-cluster pairs clear
    the pi(x) >= 0.75 density threshold while background noise does not.

    Calibrating on the low percentile of NN distances (not the median)
    matters in high dimension: uniform noise distances CONCENTRATE, so a
    median-based k gives every noise pair affinity ~0.8 and the whole noise
    cloud becomes one spurious "dominant cluster". The 10th percentile tracks
    the dense (cluster) scale; noise then decays to ~0 affinity.

    The subsample is STRIDED (row i·n/m with fractional striding, so the
    picks span [0, n) for every n — an integer stride n//m truncates to 1
    for sample <= n < 2·sample and degenerates back to the prefix), not a
    prefix: point order is often spatially meaningful (generated
    cluster-by-cluster, or sorted by LSH projection in the ShardedStore), so
    a prefix is one spatially-coherent corner whose NN distances skew the
    percentile. The indices mirror `source.strided_sample_indices`, which is
    how chunked / out-of-core engines draw the SAME rows without
    materializing v.
    """
    n = v.shape[0]
    m = min(sample, n)
    # indices are static (shape-derived) — build them host-side in int64 so
    # i*n cannot overflow int32 for multi-million-row datasets
    s = v[(np.arange(m, dtype=np.int64) * n) // m]
    d = pairwise_distance(s, s, 2.0, backend)
    d = d + jnp.where(jnp.eye(m, dtype=bool), jnp.inf, 0.0)
    nn = jnp.min(d, axis=1)
    ref = jnp.percentile(nn, percentile)
    return jnp.log(1.0 / target) / jnp.maximum(ref, 1e-12)
