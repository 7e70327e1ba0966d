"""Device time of the `lid_sweep` kernel per peel-loop round, in ms."""

import xtrace

KERNEL = r"^lid_sweep_pallas$"


def read(run):
    ns, launches = xtrace.kernel_ns(run.summary, KERNEL)
    rounds = run.counters.get("rounds")
    if launches == 0 or not rounds:
        return None
    return ns / 1e6 / (rounds * run.counters["fits"])
