"""Time the serving worker is blocked on the device's answer per batch,
in ms: the `serve.wait` spans' total over the `serve.batch` count
(`repro.utils.trace`; nothing on a program that records no such
spans)."""


def read(run):
    try:
        from repro.utils import trace
    except ImportError:
        return None
    s = trace.summary()
    batches = s.get("serve.batch", (0, 0, 0))[0]
    if not batches:
        return None
    return s.get("serve.wait", (0, 0, 0))[1] / 1e6 / batches
