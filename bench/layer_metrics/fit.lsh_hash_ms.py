"""Device time of the `lsh_hash` kernel in one whole fit, in ms: hashing
the points at the LSH build and the support rows at every CIVS probe."""

import xtrace

KERNEL = r"^lsh_hash_pallas$"


def read(run):
    ns, launches = xtrace.kernel_ns(run.summary, KERNEL)
    if launches == 0:
        return None
    return ns / 1e6 / run.counters["fits"]
