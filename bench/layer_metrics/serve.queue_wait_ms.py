"""Mean time a request waits in the server's queue before it is packed,
in ms: `ServingStats` queue_wait_s / served over the traced window."""


def read(run):
    if not run.counters.get("batches"):
        return None
    return run.counters["queue_wait_ms"]
