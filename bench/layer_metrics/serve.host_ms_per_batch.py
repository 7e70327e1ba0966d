"""Host time of the serving worker per batch, in ms: the `serve.batch`
spans' total (pop to the last future resolved) less the time blocked on
the device's answer (`serve.wait`), over the batches
(`repro.utils.trace`; nothing on a program that records no such
spans)."""


def read(run):
    try:
        from repro.utils import trace
    except ImportError:
        return None
    s = trace.summary()
    batches, total_ns, _ = s.get("serve.batch", (0, 0, 0))
    if not batches:
        return None
    return (total_ns - s.get("serve.wait", (0, 0, 0))[1]) / 1e6 / batches
