"""Share of valid seeds whose claims become an accepted cluster, in %:
`alid.clusters_accepted` over `alid.seeds_valid`, summed over the traced
fit's rounds (`repro.utils.trace`; nothing on a program that records no
such counters)."""


def read(run):
    try:
        from repro.utils import trace
    except ImportError:
        return None
    s = trace.summary()
    seeds = s.get("alid.seeds_valid", (0, 0, 0))[0]
    if not seeds:
        return None
    return 100.0 * s.get("alid.clusters_accepted", (0, 0, 0))[0] / seeds
