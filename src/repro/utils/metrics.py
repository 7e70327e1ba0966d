"""Detection-quality metrics. The paper evaluates with AVG-F (Chen & Saad,
TKDE'12): the mean, over TRUE dominant clusters, of the best F1 achieved by
any detected cluster."""

from __future__ import annotations

import numpy as np


def _contingency(a: np.ndarray, b: np.ndarray):
    """Overlap counts of the clusters (labels >= 0) of two labelings:
    (table (A, B) int64, sizes of a's clusters, sizes of b's clusters),
    rows/columns in ascending label order. One pass over the points."""
    a, b = np.asarray(a), np.asarray(b)
    a_ids, a_inv = np.unique(a, return_inverse=True)
    b_ids, b_inv = np.unique(b, return_inverse=True)
    table = np.zeros((a_ids.size, b_ids.size), np.int64)
    np.add.at(table, (a_inv, b_inv), 1)
    a_keep, b_keep = a_ids >= 0, b_ids >= 0
    return (table[np.ix_(a_keep, b_keep)], table.sum(1)[a_keep],
            table.sum(0)[b_keep])


def best_f1_per_cluster(true_labels: np.ndarray,
                        pred_labels: np.ndarray) -> np.ndarray:
    """For each true cluster (ascending id; noise = -1 on both sides), the
    best F1 = 2|t∩p| / (|t| + |p|) over detected clusters, 0 if none
    overlaps. One contingency table, O(n + T·P)."""
    table, t_size, p_size = _contingency(true_labels, pred_labels)
    if p_size.size == 0:
        return np.zeros(t_size.shape, np.float64)
    f1 = 2.0 * table / (t_size[:, None] + p_size[None, :])
    return f1.max(axis=1)


def avg_f1_score(true_labels: np.ndarray, pred_labels: np.ndarray) -> float:
    """AVG-F: the mean over true clusters of their best F1."""
    best = best_f1_per_cluster(true_labels, pred_labels)
    return float(best.mean()) if best.size else 0.0


def canonical_labels(labels: np.ndarray) -> np.ndarray:
    """Renumber cluster ids by first occurrence (noise -1 kept), so two
    clusterings compare exactly regardless of label permutation."""
    out = np.full_like(labels, -1)
    mapping: dict[int, int] = {}
    for i, v in enumerate(labels):
        if v >= 0:
            out[i] = mapping.setdefault(int(v), len(mapping))
    return out


def label_agreement(a: np.ndarray, b: np.ndarray) -> float:
    """Fraction of points with the same canonical label (1.0 = identical
    clustering up to relabeling) — the replicated/sharded parity metric."""
    return float(np.mean(canonical_labels(a) == canonical_labels(b)))


def matched_agreement(a: np.ndarray, b: np.ndarray) -> float:
    """Fraction of points on which two labelings agree after matching their
    cluster ids one-to-one by overlap (greedy, largest overlap first); noise
    (-1) matches only noise. Unlike `label_agreement` it tolerates a
    different discovery order, so one early cluster that differs does not
    shift every later id."""
    a, b = np.asarray(a), np.asarray(b)
    table, _, _ = _contingency(a, b)
    agree = int(np.sum((a < 0) & (b < 0)))
    used_a, used_b = set(), set()
    for flat in np.argsort(-table, axis=None, kind="stable"):
        i, j = divmod(int(flat), table.shape[1])
        if table[i, j] == 0:
            break
        if i in used_a or j in used_b:
            continue
        used_a.add(i)
        used_b.add(j)
        agree += int(table[i, j])
    return agree / max(a.size, 1)
