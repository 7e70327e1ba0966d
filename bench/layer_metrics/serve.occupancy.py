"""Batch occupancy of the serving loop over the traced window, %:
`ServingStats` slots filled / (batches x batch_slots)."""


def read(run):
    if not run.counters.get("batches"):
        return None
    return 100.0 * run.counters["occupancy"]
