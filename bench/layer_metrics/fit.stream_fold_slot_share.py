"""Share of the old per-shard fold width that the streamed engine's shard
folds still gather, in %: `alid.stream.fold_slots` (the probe-window
slots the routed shards' folds gathered) over `alid.stream.shards_routed`
times seeds_per_round x a_cap x n_tables x probe (what a fold over every
candidate slot of every lane would have worked on), both summed over the
outer iterations of the traced fit (`repro.utils.trace`; nothing on a
program that records no such counters)."""


def read(run):
    try:
        from repro.utils import trace
    except ImportError:
        return None
    s = trace.summary()
    routed = s.get("alid.stream.shards_routed", (0, 0, 0))[0]
    if "alid.stream.fold_slots" not in s or not routed:
        return None
    conf = run.cell.config
    width = (conf["alid"]["seeds_per_round"] * conf["alid"]["a_cap"]
             * conf["lsh"]["n_tables"] * conf["lsh"]["probe"])
    return 100.0 * s["alid.stream.fold_slots"][0] / (routed * width)
