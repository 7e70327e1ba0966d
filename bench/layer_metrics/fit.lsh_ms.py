"""Device time of the LSH layer in one whole fit, in ms: the self time of
every device op made by the program's LSH modules (`repro/lsh/`, the
`lsh_hash` kernel): the build, and the hashing and bucket-window probes
of every CIVS pass."""

import xtrace

SOURCE = r"/repro/lsh/[^/]+\.py$|/repro/kernels/lsh_hash\.py$"


def read(run):
    ns, ops = xtrace.source_ns(run.summary, SOURCE)
    if ops == 0:
        return None
    return ns / 1e6 / run.counters["fits"]
