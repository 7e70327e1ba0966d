"""Peel-loop rounds of the traced fit (`Clustering.n_rounds`, the
driver's round counter in `core/engine.py` `_fit_loop`)."""


def read(run):
    rounds = run.counters.get("rounds")
    return None if rounds is None else float(rounds)
