"""The `fit.stream100k` reader `fit.stream_fold_slot_share`, on counters
made by hand and on a program without the recorder."""

import os
import sys

import pytest

import harness

NAME = "fit.stream_fold_slot_share"


def reader():
    return harness.load_module(
        os.path.join(harness.BENCH, "layer_metrics", NAME + ".py"),
        "test_fold_slots_" + NAME.replace(".", "_"))


class Run:
    cell = harness.load_cell("fit.stream100k")
    counters = {"fits": 1}


@pytest.fixture
def recorder(monkeypatch):
    """The recorder, recording without a profiler."""
    from repro.utils import trace
    trace.reset()
    monkeypatch.setattr(trace, "recording", lambda: True)
    yield trace
    trace.reset()


def test_fold_slot_share_by_hand(recorder):
    # three passes: 8, 8 and 4 routed shards, whose folds gathered 190,054,
    # 380,109 and 0 slots, of a full fold's 32 x 232 x 4 x 16 = 475,136
    for routed, slots in ((8, 190_054), (8, 380_109), (4, 0)):
        recorder.count("alid.stream.shards_routed", routed)
        recorder.count("alid.stream.fold_slots", slots)
    want = 100.0 * (190_054 + 380_109) / (20 * 475_136)
    assert reader().read(Run) == pytest.approx(want, rel=1e-12)


def test_fold_slot_share_reads_nothing_without_its_counter(recorder,
                                                           monkeypatch):
    # a program that routes but does not count its fold slots gives no
    # reading, nor does one that records nothing
    recorder.count("alid.stream.shards_routed", 8)
    assert reader().read(Run) is None
    recorder.reset()
    assert reader().read(Run) is None
    import repro.utils
    monkeypatch.delattr(repro.utils, "trace", raising=False)
    monkeypatch.setitem(sys.modules, "repro.utils.trace", None)
    assert reader().read(Run) is None
