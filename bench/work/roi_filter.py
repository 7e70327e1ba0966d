"""Work of one `roi_filter` launch (`repro.kernels.roi_filter`): for each
of `batch` seeds, the distance of `n` candidate rows of width `d` to the
ROI center, the radius test and the masked score.

Bytes: each candidate row once (f32), its int32 validity flag, the
center, and the f32 distance and score written back. Operations per
candidate: d subtractions, d multiplies and d adds for the squared
distance, a square root, the comparison and the select."""


def count(batch: int, n: int, d: int) -> tuple[float, float]:
    """(flops, HBM bytes)."""
    flops = batch * n * (3 * d + 3)
    hbm = batch * (4 * n * d + 4 * n + 4 * d + 4 + 2 * 4 * n)
    return float(flops), float(hbm)
