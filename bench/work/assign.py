"""Work of one `assign` launch (`repro.kernels.assign`): `m` query rows
scored against `clusters` supports of `cap` rows of width `d`, the argmax
and the density bar.

Counted as the algorithm needs it, whatever the kernel's tiling: every
support row and weight read once, the query rows once, the densities, and
the label and best score written back. Operations: the cross term
2 * m * clusters * cap * d, the support norms 3 * clusters * cap * d
(squares and a sum), the query norms 2 * m * d, and per (query, support
row) eight more: the distance from the expansion (3), square root, scale,
exponential and the weighted sum (2)."""


def count(m: int, clusters: int, cap: int, d: int) -> tuple[float, float]:
    """(flops, HBM bytes)."""
    flops = (2 * m * clusters * cap * d + 3 * clusters * cap * d
             + 2 * m * d + 8 * m * clusters * cap)
    hbm = (4 * clusters * cap * (d + 1) + 4 * m * d + 4 * clusters
           + 2 * 4 * m)
    return float(flops), float(hbm)
