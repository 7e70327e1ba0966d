"""Share of its roofline that the `roi_filter` kernel reaches in the
streamed engine's traced fit, in %: the least time the chip needs for the
launches the trace shows (work/roi_filter.py, from the configuration's
shapes: each routed shard's fold filters a_cap * n_tables * probe
candidates for each of the round's seeds in one launch, which the
compiler names after the vmapped jit it sits in) over their device
time."""

import peaks
import xtrace

KERNEL = r"^vmap_jit_roi_filter_pallas_"


def read(run):
    ns, launches = xtrace.kernel_ns(run.summary, KERNEL)
    if launches == 0 or ns <= 0:
        return None
    conf = run.cell.config
    cands = (conf["alid"]["a_cap"] * conf["lsh"]["n_tables"]
             * conf["lsh"]["probe"])
    flops, hbm = run.work("roi_filter").count(
        conf["alid"]["seeds_per_round"], cands, conf["d"])
    least, _ = peaks.roofline_s(flops, hbm, run.peaks)
    return 100.0 * launches * least / (ns / 1e9)
