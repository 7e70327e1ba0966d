"""Share of the ALID iterations executed by the vmapped seed lanes that a
valid lane needed, in %: `alid.lane_iters_useful` (each valid lane's own
outer iterations) over `alid.lane_iters_executed` (lanes x the slowest
lane's iterations, which every lane runs) (`repro.utils.trace`; nothing on
a program that records no such counters)."""


def read(run):
    try:
        from repro.utils import trace
    except ImportError:
        return None
    s = trace.summary()
    executed = s.get("alid.lane_iters_executed", (0, 0, 0))[0]
    if not executed:
        return None
    return 100.0 * s.get("alid.lane_iters_useful", (0, 0, 0))[0] / executed
