"""Share of the store's shards that the streamed engine's routing sends
to the device, in %: `alid.stream.shards_routed` (shards whose bounding
ball meets some live lane's ROI ball) over `alid.stream.shards_total`
(the store's shards), both summed over the outer iterations of the traced
fit (`repro.utils.trace`; nothing on a program that records no such
counters)."""


def read(run):
    try:
        from repro.utils import trace
    except ImportError:
        return None
    s = trace.summary()
    total = s.get("alid.stream.shards_total", (0, 0, 0))[0]
    if not total:
        return None
    return 100.0 * s.get("alid.stream.shards_routed", (0, 0, 0))[0] / total
