"""The benchmark's own copy of the synthetic deployment: data, LSH segment
length and the Laplacian scale k, made from a seed.

Copied from the program so that later changes to the program cannot move
the yardstick: `make_blobs_with_noise` and `sample_nn_distances`
(`repro.data.synthetic`), the segment-length rule of
`repro.launch.run_palid.synthetic_deployment`, and the k rule of
`repro.core.affinity.estimate_k` on `repro.core.source.strided_sample_indices`.
The random streams are the program's own, so a seed gives the same points
as `run_palid --n N --d D --clusters C` with that seed.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

K_SAMPLE = 512          # strided rows the engine samples for k
K_TARGET = 0.95         # affinity of a cluster-scale nearest-neighbour pair
K_PERCENTILE = 10.0


class Deployment(NamedTuple):
    points: np.ndarray      # (n, d) f32, shuffled
    labels: np.ndarray      # (n,) int32 planted cluster, -1 = noise
    means: np.ndarray       # (clusters, d) f64
    covs: np.ndarray        # (clusters, d) f64 diagonal variances
    cluster_size: int


def make_blobs_with_noise(n_clusters: int, cluster_size: int, n_noise: int,
                          d: int, seed: int, mean_range: float = 50.0,
                          cov_max: float = 10.0, overlap_pairs: int = 2,
                          noise_range: float = 60.0) -> Deployment:
    """Gaussian blobs with random diagonal covariances (two pairs of them
    overlapping) in uniform background noise, shuffled."""
    rng = np.random.default_rng(seed)
    means = rng.uniform(-mean_range, mean_range, size=(n_clusters, d))
    for j in range(min(overlap_pairs, n_clusters // 2)):
        means[2 * j + 1] = means[2 * j] + rng.normal(0, 3.0, size=d)
    covs = rng.uniform(0.0, cov_max, size=(n_clusters, d))
    pts, labels = [], []
    for c in range(n_clusters):
        pts.append(means[c] + rng.normal(size=(cluster_size, d))
                   * np.sqrt(covs[c]))
        labels.append(np.full(cluster_size, c))
    if n_noise > 0:
        pts.append(rng.uniform(-noise_range, noise_range, size=(n_noise, d)))
        labels.append(np.full(n_noise, -1))
    points = np.concatenate(pts).astype(np.float32)
    labels = np.concatenate(labels).astype(np.int32)
    perm = rng.permutation(points.shape[0])
    return Deployment(points[perm], labels[perm], means, covs, cluster_size)


def deployment(n: int, d: int, clusters: int, member_share: float,
               seed: int) -> Deployment:
    """`clusters` planted blobs holding `member_share` of the n points."""
    cluster_size = max(4, int(n * member_share) // clusters)
    return make_blobs_with_noise(clusters, cluster_size,
                                 n - clusters * cluster_size, d, seed)


def sample_nn_distances(points: np.ndarray, sample: int = 512,
                        seed: int = 0) -> np.ndarray:
    """Nearest-neighbour distance of each of `sample` seeded rows, within
    that sample (f64)."""
    rng = np.random.default_rng(seed)
    m = min(sample, points.shape[0])
    s = points[rng.choice(points.shape[0], size=m, replace=False)]
    d2 = pairwise_sq_dist(s, s)
    np.fill_diagonal(d2, np.inf)
    return np.sqrt(d2.min(axis=1))


def seg_len(points: np.ndarray, scale: float, percentile: float) -> float:
    """LSH segment length: `scale` x the `percentile` of sampled
    nearest-neighbour distances."""
    return scale * float(np.percentile(sample_nn_distances(points),
                                       percentile))


def laplacian_k(points: np.ndarray) -> float:
    """k with exp(-k * r) = K_TARGET at r = the K_PERCENTILE of nearest-
    neighbour distances within K_SAMPLE evenly strided rows (f64)."""
    n = points.shape[0]
    m = min(K_SAMPLE, n)
    s = points[(np.arange(m, dtype=np.int64) * n) // m]
    d2 = pairwise_sq_dist(s, s)
    np.fill_diagonal(d2, np.inf)
    ref = np.percentile(np.sqrt(d2.min(axis=1)), K_PERCENTILE)
    return float(np.log(1.0 / K_TARGET) / max(ref, 1e-12))


def pairwise_sq_dist(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Squared Euclidean distances in f64, (m, d) x (n, d) -> (m, n)."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    d2 = ((a * a).sum(1)[:, None] + (b * b).sum(1)[None, :]
          - 2.0 * a @ b.T)
    return np.maximum(d2, 0.0)


def fresh_queries(dep: Deployment, count: int, member_share: float,
                  noise_range: float, rng: np.random.Generator
                  ) -> np.ndarray:
    """`count` new draws from the deployment's generator: a member of a
    uniformly chosen planted blob with probability `member_share`, else
    uniform noise. (count, d) f32."""
    d = dep.means.shape[1]
    member = rng.random(count) < member_share
    which = rng.integers(0, dep.means.shape[0], size=count)
    q = rng.uniform(-noise_range, noise_range, size=(count, d))
    m = np.flatnonzero(member)
    q[m] = (dep.means[which[m]]
            + rng.normal(size=(m.size, d)) * np.sqrt(dep.covs[which[m]]))
    return q.astype(np.float32)


def planted_supports(dep: Deployment, cap: int):
    """Each planted cluster's members as its support, padded to `cap` with
    index -1, weight 0 and zero rows: (support_idx (C, cap) int32,
    support_w (C, cap) f32 uniform over members, support_v (C, cap, d) f32).
    """
    n_clusters, d = dep.means.shape
    order = np.argsort(dep.labels, kind="stable")
    first = np.searchsorted(dep.labels[order], 0)
    members = order[first:].reshape(n_clusters, dep.cluster_size)
    idx = np.full((n_clusters, cap), -1, np.int32)
    idx[:, :dep.cluster_size] = members
    w = np.zeros((n_clusters, cap), np.float32)
    w[:, :dep.cluster_size] = 1.0 / dep.cluster_size
    v = np.zeros((n_clusters, cap, d), np.float32)
    v[:, :dep.cluster_size] = dep.points[members]
    return idx, w, v


def support_densities(sup_v: np.ndarray, sup_w: np.ndarray,
                      k: float) -> np.ndarray:
    """w^T A w of every support in plain float32 jax.numpy at full matmul
    precision, A_ij = exp(-k ||v_i - v_j||) off the diagonal."""
    return np.asarray(_densities(jnp.asarray(sup_v), jnp.asarray(sup_w),
                                 jnp.float32(k)))


@jax.jit
def _densities(sup_v, sup_w, k):
    def one(vw):
        v, w = vw
        sq = jnp.sum(v * v, -1)
        d2 = sq[:, None] + sq[None, :] - 2.0 * jnp.matmul(
            v, v.T, precision=jax.lax.Precision.HIGHEST)
        a = jnp.exp(-k * jnp.sqrt(jnp.maximum(d2, 0.0)))
        a = jnp.where(jnp.eye(v.shape[0], dtype=bool), 0.0, a)
        return jnp.sum(w * jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST))

    return jax.lax.map(one, (sup_v, sup_w))


def noise_threshold(dep: Deployment, sup_v: np.ndarray, sup_w: np.ndarray,
                    dens: np.ndarray, k: float, quantile: float, count: int,
                    noise_range: float, rng: np.random.Generator) -> float:
    """The acceptance threshold that turns away `quantile` of fresh noise
    queries: that quantile, over `count` of them, of the best weighted
    support affinity s_c = sum_j w_cj exp(-k ||q - v_cj||) over the
    density of the cluster that gives it (host, float64)."""
    q = fresh_queries(dep, count, 0.0, noise_range, rng)
    scores = np.stack([np.exp(-k * np.sqrt(pairwise_sq_dist(q, v))) @ w
                       for v, w in zip(sup_v, np.asarray(sup_w, np.float64))],
                      axis=1)
    best = scores.argmax(axis=1)
    ratio = scores[np.arange(count), best] / np.asarray(dens, np.float64)[best]
    return float(np.float32(np.quantile(ratio, quantile)))
