"""Engine build time of one fit, in ms: the `alid.build` span (k from a
sample on the host, the upload, the LSH tables, up to the bucket sizes on
the host) over the fits traced (`repro.utils.trace`; nothing on a program
that records no such spans)."""


def read(run):
    try:
        from repro.utils import trace
    except ImportError:
        return None
    s = trace.summary()
    fits = s.get("alid.fit", (0, 0, 0))[0]
    if not fits or "alid.build" not in s:
        return None
    return s["alid.build"][1] / 1e6 / fits
