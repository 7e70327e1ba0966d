"""Device time of CIVS candidate retrieval in one whole fit, in ms: the
self time of every device op made by `repro/core/civs.py` (gathering the
probed candidates' rows and ids, de-duplicating them, every pass)."""

import xtrace

SOURCE = r"/repro/core/civs\.py$"


def read(run):
    ns, ops = xtrace.source_ns(run.summary, SOURCE)
    if ops == 0:
        return None
    return ns / 1e6 / run.counters["fits"]
