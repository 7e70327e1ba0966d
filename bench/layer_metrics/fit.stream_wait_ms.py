"""Time the streamed engine's compute loop waits on the shard pipeline in
one whole fit, in ms: the `pipeline.wait` spans (the host blocked on the
slot ring's head while the reader thread reads and uploads the next
routed shard) over the fits traced (`repro.utils.trace`; nothing on a
program that records no such spans)."""


def read(run):
    try:
        from repro.utils import trace
    except ImportError:
        return None
    s = trace.summary()
    if "pipeline.wait" not in s:
        return None
    return s["pipeline.wait"][1] / 1e6 / run.counters["fits"]
