"""Plain references that decide `correct`. They import nothing of the
program and take nothing it made except the answers under test.

Fit: a `Clustering` is checked cluster by cluster against the data the
benchmark generated. Each cluster's density pi = w^T A w, with
A_ij = exp(-k ||v_i - v_j||) off the diagonal, is recomputed in float64 from
the points at its support indices, its exported weights and the benchmark's
own k; the stored support rows must be those points; every labelled point
must lie in its cluster's support; and the planted clusters must be found.

Serving: the weighted support affinity of each sampled query to every
cluster, s_c = sum_j w_cj exp(-k ||q - v_cj||), in float32 at full matmul
precision; a query's answer is argmax_c s_c when s_best >= threshold *
density_best, else -1. `answer_gaps` says by how much the served answer
falls short of the reference's in those scores.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from deploy import pairwise_sq_dist


# ------------------------------------------------------------------ fit --
def densities_f64(points: np.ndarray, support_idx: np.ndarray,
                  support_w: np.ndarray, k: float) -> np.ndarray:
    """w^T A w of every cluster, from the points at its support indices."""
    out = np.empty(support_idx.shape[0], np.float64)
    for c in range(support_idx.shape[0]):
        live = support_idx[c] >= 0
        idx = support_idx[c][live]
        w = np.asarray(support_w[c][live], np.float64)
        a = np.exp(-k * np.sqrt(pairwise_sq_dist(points[idx], points[idx])))
        np.fill_diagonal(a, 0.0)
        out[c] = w @ a @ w
    return out


def best_f1_per_cluster(true_labels: np.ndarray,
                        pred_labels: np.ndarray) -> np.ndarray:
    """For each planted cluster, the best F1 over detected clusters."""
    t_ids, t_inv = np.unique(true_labels, return_inverse=True)
    p_ids, p_inv = np.unique(pred_labels, return_inverse=True)
    table = np.zeros((t_ids.size, p_ids.size), np.int64)
    np.add.at(table, (t_inv, p_inv), 1)
    t_keep, p_keep = t_ids >= 0, p_ids >= 0
    t_size, p_size = table.sum(1)[t_keep], table.sum(0)[p_keep]
    table = table[np.ix_(t_keep, p_keep)]
    if p_size.size == 0:
        return np.zeros(t_size.shape, np.float64)
    return (2.0 * table / (t_size[:, None] + p_size[None, :])).max(axis=1)


def fit_numbers(points: np.ndarray, planted: np.ndarray, res,
                k_ref: float) -> dict:
    """The compared numbers of one fit result, by name."""
    labels = np.asarray(res.labels)
    dens = np.asarray(res.densities, np.float64)
    sidx = np.asarray(res.support_idx)
    sw = np.asarray(res.support_w)
    sv = np.asarray(res.support_v)
    n_clusters = dens.shape[0]
    out = {"clusters": n_clusters}
    if n_clusters == 0:
        out["planted_found"] = 0
        return out
    ref = densities_f64(points, sidx, sw, k_ref)
    rel = (dens - ref) / ref
    out["density_gap_max"] = float(np.abs(rel).max())
    out["density_bias"] = float(abs(rel.mean()))
    out["k_gap"] = abs(float(res.k) - k_ref) / k_ref
    live = sidx >= 0
    want_v = np.where(live[..., None], points[np.clip(sidx, 0, None)], 0.0)
    out["support_rows_wrong"] = int((sv != want_v).any(axis=2).sum())
    # every labelled point lies in the support of its cluster
    member = np.zeros(labels.shape, bool)
    lab = labels >= 0
    rows = labels[lab]
    pts = np.flatnonzero(lab)
    in_sup = (sidx[rows] == pts[:, None]).any(axis=1)
    member[pts] = in_sup
    out["labels_outside_support"] = int(lab.sum() - member.sum())
    best = best_f1_per_cluster(planted, labels)
    out["planted_found"] = int((best >= 0.5).sum())
    out["avg_f"] = float(best.mean())
    return out


# -------------------------------------------------------------- serving --
@functools.partial(jax.jit, static_argnames=("low",))
def _scores(q, v, w, v2, k, low: bool):
    prec = jax.lax.Precision.DEFAULT if low else jax.lax.Precision.HIGHEST
    cdt = jnp.bfloat16 if low else jnp.float32
    qc = q.astype(cdt)
    q2 = jnp.sum(jnp.square(qc.astype(jnp.float32)), -1)
    cross = jnp.einsum("md,cad->mca", qc, v.astype(cdt), precision=prec,
                       preferred_element_type=jnp.float32)
    d2 = q2[:, None, None] + v2[None] - 2.0 * cross
    aff = jnp.exp(-k * jnp.sqrt(jnp.maximum(d2, 0.0)))
    return jnp.einsum("mca,ca->mc", aff, w, precision=prec)


def support_scores(queries, sup_v, sup_w, k: float, block: int = 128,
                   dtype: str = "float32") -> np.ndarray:
    """(m, C) weighted support affinities on the default device, `block`
    queries at a time. dtype "float32" contracts at full precision;
    "bfloat16" rounds the inputs to bfloat16 and contracts in one
    bfloat16 pass (the control)."""
    low = dtype == "bfloat16"
    v = jnp.asarray(sup_v, jnp.float32)
    w = jnp.asarray(sup_w, jnp.float32)
    vc = v.astype(jnp.bfloat16).astype(jnp.float32) if low else v
    v2 = jnp.sum(jnp.square(vc), -1)
    kk = jnp.float32(k)
    out = []
    for i in range(0, len(queries), block):
        q = np.asarray(queries[i:i + block], np.float32)
        pad = block - q.shape[0]
        q = np.pad(q, ((0, pad), (0, 0)))
        out.append(np.asarray(_scores(jnp.asarray(q), v, w, v2, kk,
                                      low=low))[:block - pad])
    return np.concatenate(out) if out else np.zeros((0, w.shape[0]))


def labels_from_scores(scores: np.ndarray, dens: np.ndarray,
                       threshold: float) -> np.ndarray:
    best = scores.argmax(axis=1)
    ok = scores[np.arange(len(best)), best] >= threshold * dens[best]
    return np.where(ok, best, -1).astype(np.int32)


def answer_gaps(served: np.ndarray, scores: np.ndarray, dens: np.ndarray,
                threshold: float) -> np.ndarray:
    """Per query, how far the served label falls short of the reference's
    answer, relative to the scores that decide it (0 where they agree):

      a cluster c served where the reference's best b scores higher:
          (s_b - s_c) / s_b;
      a cluster c served whose score is under its bar t * dens_c:
          (t * dens_c - s_c) / (t * dens_c);
      -1 served where the best clears its bar:
          (s_b - t * dens_b) / (t * dens_b).
    """
    m = len(served)
    best = scores.argmax(axis=1)
    s_best = scores[np.arange(m), best]
    bar_best = threshold * dens[best]
    gap = np.zeros(m, np.float64)
    none = served < 0
    gap[none] = np.maximum(s_best[none] - bar_best[none], 0.0) / bar_best[none]
    c = served[~none]
    s_c = scores[np.flatnonzero(~none), c]
    bar_c = threshold * dens[c]
    g_arg = (s_best[~none] - s_c) / np.maximum(s_best[~none], 1e-30)
    g_bar = np.maximum(bar_c - s_c, 0.0) / bar_c
    gap[~none] = np.maximum(g_arg, g_bar)
    return gap
