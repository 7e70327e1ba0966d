"""The trace reduction, on hand-made events with hand-counted answers and
on a small trace recorded on one v5e (tests/data, the serving path at a
low rate)."""

import os

import pytest

import xtrace
from xtrace import Event, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def hand_trace():
    # device: a loop holding a fusion and a kernel, the kernel again
    # alone, and a copy that runs past the window
    dev = [Event(10, 40, "while"), Event(12, 20, "fusion"),
           Event(25, 35, "assign_pallas"), Event(60, 70, "assign_pallas"),
           Event(95, 120, "copy")]
    spans = [Event(0, 100, "bench.window"), Event(40, 60, "bench.submit"),
             Event(70, 95, "bench.sleep"), Event(75, 80, "bench.submit")]
    return Trace([dev], spans)


def test_busy_and_gaps_by_hand():
    tr = hand_trace()
    s = xtrace.summarize(tr, *xtrace.window(tr, "bench.window"))
    # busy in [0, 100]: [10, 40] + [60, 70] + [95, 100] = 45
    assert s.window_ns == 100 and s.busy_ns == 45
    # gaps: [0, 10] none-but-window, [40, 60] submit, [70, 95] sleep
    assert sorted(s.gaps, key=lambda g: -g[1]) == s.gaps
    assert s.gaps == [("bench.sleep", 25), ("bench.submit", 20),
                      ("bench.window", 10)]


def test_self_time_by_source_by_hand():
    tr = hand_trace()
    dev = [e._replace(source="/x/repro/lsh/pstable.py") if e.name == "fusion"
           else e._replace(source="/x/repro/kernels/assign.py")
           for e in tr.devices[0]]
    s = xtrace.summarize(tr._replace(devices=[dev]), 0, 100)
    # the while's self time (12) and the kernels (20) and copy (5) are
    # assign.py's; the fusion inside the while (8) is pstable.py's
    assert s.sources["/x/repro/lsh/pstable.py"] == (8, 1)
    assert s.sources["/x/repro/kernels/assign.py"] == (37, 4)
    assert xtrace.source_ns(s, r"/repro/lsh/") == (8, 1)


def test_self_time_by_op_name_by_hand():
    tr = hand_trace()
    s = xtrace.summarize(tr, 0, 100)
    assert s.ops["while"] == (30 - 8 - 10, 1)   # less what runs inside
    assert s.ops["fusion"] == (8, 1)
    assert xtrace.kernel_ns(s, r"^assign_pallas$") == (20, 2)
    assert s.ops["copy"] == (5, 1)               # clipped to the window
    bd = xtrace.breakdown(s)
    assert bd["device_ops"][0] == ["assign_pallas", 20e-9]
    assert bd["idle_gaps"][0] == ["bench.sleep", 25e-9]


def test_op_names_from_hlo_text():
    assert xtrace.op_name("%roi_filter_pallas.8 = (f32[32,14848,1]) "
                          "custom-call(f32[32,1,1] %copy-done.43)") \
        == "roi_filter_pallas"
    assert xtrace.op_name("%fusion.179 = u32[29696]{0:T(1024)S(1)} "
                          "fusion(...)") == "fusion u32[29696]"
    assert xtrace.op_name("%copy-start.2 = (f32[200]{0:T(256)}, u32[]) "
                          "copy-start(...)") == "copy-start f32[200]"


def test_innermost_span_names_the_gap():
    tr = hand_trace()
    # a gap around t = 77 lies in bench.sleep and the nested bench.submit
    assert xtrace.host_span_at(tr.spans, 77) == "bench.submit"
    assert xtrace.host_span_at(tr.spans, 150) == "none"


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="device plane"):
        xtrace.summarize(Trace([], []), 0, 1)


def test_layer_readers_on_a_hand_summary():
    import harness
    import peaks
    cell = harness.load_cell("serve.blobs1m.r80")
    summary = xtrace.Summary(
        window_ns=4e6, busy_ns=1e6,
        ops={"assign_pallas": (1e6, 2), "fusion": (1e5, 4)}, gaps=[])
    counters = {"occupancy": 0.25, "queue_wait_ms": 1.5, "batches": 2,
                "batch_slots": 64, "clusters": 200, "cap": 2160, "d": 128}
    got = harness.read_layer_metrics(cell, counters, summary,
                                     peaks.peaks("TPU v5 lite"))
    assert got["serve.occupancy"] == {"value": 25.0, "unit": "%"}
    assert got["serve.queue_wait_ms"]["value"] == 1.5
    assert got["device.idle_share.serve"]["value"] == 75.0
    # two launches of 200 x 2160 x 129 x 4 B (+ small) at 819 GB/s in 1 ms
    share = got["serve.assign_roofline"]["value"]
    assert abs(share - 2 * 200 * 2160 * 129 * 4 / 819e9 / 1e-3 * 100) < 0.01


def test_a_reader_with_nothing_to_read_is_left_out():
    import harness
    import peaks
    cell = harness.load_cell("fit.blobs100k")
    summary = xtrace.Summary(window_ns=1e9, busy_ns=5e8, ops={}, gaps=[])
    got = harness.read_layer_metrics(cell, {"rounds": 28, "fits": 1},
                                     summary, peaks.peaks("TPU v5 lite"))
    assert set(got) == {"fit.rounds", "device.idle_share.fit"}


def test_gap_names_the_call_the_device_waited_for():
    tr = hand_trace()._replace(dispatches=[Event(5, 6, "_map_round"),
                                           Event(55, 57, "resolve_claims")])
    s = xtrace.summarize(tr, 0, 100)
    assert s.gaps[1] == ("bench.submit > resolve_claims", 20)
    assert s.gaps[0] == ("bench.sleep > resolve_claims", 25)


def test_recorded_serving_trace():
    """47 assign launches recorded on one v5e (the serving path at 200
    requests/s for 0.25 s, Poisson arrivals, the 1M tenant)."""
    tr = xtrace.load(os.path.join(DATA, "serve_small.xplane.pb"))
    assert len(tr.devices) == 1
    t0, t1 = xtrace.window(tr, "bench.window")
    s = xtrace.summarize(tr, t0, t1)
    ns, launches = xtrace.kernel_ns(s, r"^assign_pallas$")
    # the same kernel summed straight from the raw events
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "serve_small.xplane.pb"), "rb") as f:
        raw = ProfileData.from_serialized_xspace(f.read())
    ops = [line for plane in raw.planes if plane.name == "/device:TPU:0"
           for line in plane.lines if line.name == "XLA Ops"][0]
    hits = [e for e in ops.events if e.name.startswith("%assign_pallas")
            and t0 <= e.start_ns and e.end_ns <= t1]
    assert launches == len(hits) == 47
    assert abs(ns - sum(e.duration_ns for e in hits)) < 1.0
    assert 0.45e6 < ns / launches < 0.55e6       # ~0.5 ms a launch
    assert 0.95 * s.busy_ns < ns <= s.busy_ns
    assert 0 < s.busy_ns < s.window_ns
    # every idle gap lies in a benchmark span, behind an assign launch
    assert sum(g[1] for g in s.gaps) == s.window_ns - s.busy_ns
    names = {g[0] for g in s.gaps}
    assert names <= {f"bench.{k} > _assign_masked"
                     for k in ("sleep", "submit", "drain", "window")}
    assert s.gaps[0][0] == "bench.sleep > _assign_masked"
    # by source: the kernel's ops come from kernels/assign.py, and the
    # self times by source add up to those by name
    by_src, _ = xtrace.source_ns(s, r"/repro/kernels/assign\.py$")
    assert ns <= by_src <= s.busy_ns
    assert abs(sum(v[0] for v in s.sources.values())
               - sum(v[0] for v in s.ops.values())) < 1.0
