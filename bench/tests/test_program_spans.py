"""The readers of the program's own spans and counters
(`repro.utils.trace`), on records made by hand with hand-counted answers,
and on a program that has no recorder, where they read nothing."""

import os
import sys

import pytest

import harness

READERS = ["fit.host_ms_per_round", "fit.build_ms", "fit.lane_useful_share",
           "fit.seed_yield", "serve.host_ms_per_batch",
           "serve.sync_ms_per_batch"]


def reader(name):
    return harness.load_module(
        os.path.join(harness.BENCH, "layer_metrics", name + ".py"),
        "test_layer_" + name.replace(".", "_"))


@pytest.fixture
def recorder(monkeypatch):
    """The recorder, recording without a profiler, on a clock that ticks
    1 ms a reading."""
    from repro.utils import trace
    ticks = iter(range(0, 10**9, 10**6))
    trace.reset()
    monkeypatch.setattr(trace, "recording", lambda: True)
    monkeypatch.setattr(trace, "_clock", lambda: next(ticks))
    yield trace
    trace.reset()


def test_fit_readers_by_hand(recorder):
    span = recorder.span
    with span("alid.fit"):                      # readings 0 .. 15
        with span("alid.build"):                # 1 .. 2: 1 ms
            pass
        for _ in range(2):                      # 3 .. 8, 9 .. 14: 5 ms each
            with span("alid.round"):
                with span("alid.round.wait"):   # 1 ms
                    pass
                with span("alid.round.wait"):   # 1 ms
                    pass
    recorder.count("alid.seeds_valid", 32)
    recorder.count("alid.clusters_accepted", 8)
    recorder.count("alid.lane_iters_useful", 30)
    recorder.count("alid.lane_iters_executed", 40)
    got = {n: reader(n).read(None) for n in READERS[:4]}
    assert got == {"fit.host_ms_per_round": 3.0, "fit.build_ms": 1.0,
                   "fit.lane_useful_share": 75.0, "fit.seed_yield": 25.0}


def test_serve_readers_by_hand(recorder):
    span = recorder.span
    for _ in range(4):                          # 4 ms a batch
        with span("serve.batch"):
            with span("serve.wait"):            # 1 ms
                pass
            with span("serve.resolve"):         # 1 ms
                pass
    got = {n: reader(n).read(None) for n in READERS[4:]}
    assert got == {"serve.host_ms_per_batch": 4.0,
                   "serve.sync_ms_per_batch": 1.0}


def test_readers_read_nothing_when_nothing_was_recorded():
    from repro.utils import trace
    trace.reset()
    assert all(reader(n).read(None) is None for n in READERS)


def test_readers_read_nothing_without_the_recorder(monkeypatch):
    """A program from before the recorder: the import fails, the reader
    returns None and raises nothing."""
    import repro.utils
    monkeypatch.delattr(repro.utils, "trace", raising=False)
    monkeypatch.setitem(sys.modules, "repro.utils.trace", None)
    assert all(reader(n).read(None) is None for n in READERS)
