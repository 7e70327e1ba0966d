"""Share of the traced serving window in which no operation runs on the
device, %."""


def read(run):
    s = run.summary
    return 100.0 * (1.0 - s.busy_ns / s.window_ns)
