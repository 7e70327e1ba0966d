"""Host time of one outer iteration of the streamed engine, in ms: the
`alid.stream.iter` spans' total, less the time the host spent blocked on
the device inside them (`alid.stream.wait`) and on the shard pipeline's
slot ring (`pipeline.wait`), over the number of iterations
(`repro.utils.trace`; nothing on a program that records no such spans)."""


def read(run):
    try:
        from repro.utils import trace
    except ImportError:
        return None
    s = trace.summary()
    iters, total_ns, _ = s.get("alid.stream.iter", (0, 0, 0))
    if not iters:
        return None
    blocked_ns = (s.get("alid.stream.wait", (0, 0, 0))[1]
                  + s.get("pipeline.wait", (0, 0, 0))[1])
    return (total_ns - blocked_ns) / 1e6 / iters
