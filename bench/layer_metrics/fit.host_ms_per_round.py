"""Host time of one peel-loop round, in ms: the `alid.round` spans' total
less the time the host spent blocked on the device inside them
(`alid.round.wait`), over the number of rounds (`repro.utils.trace`;
nothing on a program that records no such spans)."""


def read(run):
    try:
        from repro.utils import trace
    except ImportError:
        return None
    s = trace.summary()
    rounds, total_ns, _ = s.get("alid.round", (0, 0, 0))
    if not rounds:
        return None
    return (total_ns - s.get("alid.round.wait", (0, 0, 0))[1]) / 1e6 / rounds
