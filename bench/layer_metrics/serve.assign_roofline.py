"""Share of its roofline that the `assign` kernel reaches over the traced
serving window, in %: the least time the chip needs for each launch
(work/assign.py at the server's batch shape: batch_slots queries against
the whole tenant) over their device time."""

import peaks
import xtrace

KERNEL = r"^assign_pallas$"


def read(run):
    ns, launches = xtrace.kernel_ns(run.summary, KERNEL)
    if launches == 0 or ns <= 0:
        return None
    c = run.counters
    flops, hbm = run.work("assign").count(c["batch_slots"], c["clusters"],
                                          c["cap"], c["d"])
    least, _ = peaks.roofline_s(flops, hbm, run.peaks)
    return 100.0 * launches * least / (ns / 1e9)
