"""CPU rehearsal of the out-of-core cell `fit.stream100k` at a tiny size:
its driver runs end to end from a memmapped `.npy` and gives a `correct`
result line, with every routed shard read back from the scratch memmap
(the host cache is off, as at full size); and the cell's own per-layer
readers, on records made by hand and on a program without the recorder."""

import copy
import json
import os
import sys
import time

import jax
import pytest

import harness

CELL = "fit.stream100k"

# 4 shards of 500 rows of width 16; everything else as test_rehearsal's
# tiny fit cell
TINY = {"n": 2000, "d": 16, "clusters": 8, "data_seeds": [3],
        "lsh": {"seg_len": 57.37}, "stream": {"n_shards": 4},
        "alid": {"a_cap": 132, "delta": 32, "max_rounds": 4,
                 "seeds_per_round": 8},
        "checks": {"planted_found": {"op": ">=", "limit": 1},
                   "avg_f": {"op": ">=", "limit": 0.3}}}

STREAM_READERS = ["fit.stream_routed_share", "fit.stream_host_ms_per_iter",
                  "fit.stream_wait_ms"]


def tiny_cell() -> harness.Cell:
    cell = harness.load_cell(CELL)
    conf = copy.deepcopy(cell.config)
    for key, value in TINY.items():
        if isinstance(value, dict):
            conf[key].update(value)
        else:
            conf[key] = value
    return cell._replace(config=conf)


def reader(name):
    return harness.load_module(
        os.path.join(harness.BENCH, "layer_metrics", name + ".py"),
        "test_stream_layer_" + name.replace(".", "_"))


def test_stream_cell_rehearsal():
    cell = tiny_cell()
    assert cell.config["stream"]["cache_bytes"] == 0
    out = harness.run_cell(cell, seed=2**31 + 11, seconds=1.0, trace=False,
                           t_start=time.perf_counter(),
                           devices=jax.devices()[:1], log=lambda m: None)
    line = json.loads(json.dumps(out))
    assert line["correct"] is True, harness.check_lines(line)
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "fit_pts_per_s"}
    assert line["metrics"]["fit_pts_per_s"]["value"] > 0
    fits = line["notes"]["pipeline"]
    assert len(fits) == line["attempted"]
    for stats in fits:
        assert stats["cache_hits"] == stats["source_reads"] == 0
        assert stats["scratch_reads"] == stats["shards_streamed"] > 0


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


class Run:
    counters = {"fits": 2}


def test_stream_readers_by_hand(recorder):
    span = recorder.span
    for routed in (8, 6, 4, 2):                 # four outer iterations
        recorder.count("alid.stream.shards_routed", routed)
        recorder.count("alid.stream.shards_total", 8)
        with span("alid.stream.iter"):          # 5 + routed ms
            with span("alid.stream.wait"):      # 1 ms
                pass
            for _ in range(routed // 2):
                with span("pipeline.wait"):     # 1 ms
                    pass
            with span("alid.stream.wait"):      # 1 ms
                pass
    got = {n: reader(n).read(Run) for n in STREAM_READERS}
    # 20 routed of 32; 40 ms of iterations less 8 ms of device waits and
    # 10 ms of pipeline waits, over 4; 10 ms of pipeline waits over 2 fits
    assert got == {"fit.stream_routed_share": 62.5,
                   "fit.stream_host_ms_per_iter": 5.5,
                   "fit.stream_wait_ms": 5.0}


def test_stream_readers_read_nothing_without_the_recorder(monkeypatch):
    from repro.utils import trace
    trace.reset()
    assert all(reader(n).read(Run) is None for n in STREAM_READERS)
    import repro.utils
    monkeypatch.delattr(repro.utils, "trace", raising=False)
    monkeypatch.setitem(sys.modules, "repro.utils.trace", None)
    assert all(reader(n).read(Run) is None for n in STREAM_READERS)


def test_stream_roi_filter_roofline_on_a_hand_summary():
    """The streamed fold's `roi_filter` launches, named after the vmapped
    jit they sit in, against the configuration's shapes; the replicated
    path's kernel name is not theirs."""
    import peaks
    import xtrace
    cell = harness.load_cell(CELL)
    streamed = "vmap_jit_roi_filter_pallas__ f32[32,14848,1] @kernels/x.py"
    summary = xtrace.Summary(window_ns=4e9, busy_ns=3e9, ops={
        streamed: (10e6, 10), "roi_filter_pallas": (1e6, 1)}, gaps=[])
    chip = peaks.peaks("TPU v5 lite")
    got = harness.read_layer_metrics(cell, {"rounds": 27, "fits": 1},
                                     summary, chip)
    # ten launches of 1 ms, each reading 32 x 14,848 candidate rows of
    # 128 f32 with their flags, center and outputs at 819 GB/s
    hbm = 32 * (4 * 14848 * 128 + 4 * 14848 + 4 * 128 + 4 + 8 * 14848)
    want = 100.0 * hbm / 819e9 / 1e-3
    assert abs(got["fit.stream_roi_filter_roofline"]["value"] - want) < 1e-9
    assert "fit.roi_filter_roofline" not in got
    summary = summary._replace(ops={"roi_filter_pallas": (1e6, 1)})
    got = harness.read_layer_metrics(cell, {"rounds": 27, "fits": 1},
                                     summary, chip)
    assert "fit.stream_roi_filter_roofline" not in got


def test_stream_lsh_hash_ms_on_a_hand_summary():
    """The store build's `lsh_hash` launches and the outer loop's vmapped
    ones both count, per fit; other kernels do not."""
    import peaks
    import xtrace
    cell = harness.load_cell(CELL)
    summary = xtrace.Summary(window_ns=4e9, busy_ns=3e9, ops={
        "lsh_hash_pallas": (2e6, 8),
        "vmap_jit_lsh_hash_pallas__ s32[32,256,4] @kernels/x.py": (6e6, 20),
        "vmap_jit_roi_filter_pallas__ f32[32,14848,1] @kernels/x.py":
            (9e6, 9)}, gaps=[])
    chip = peaks.peaks("TPU v5 lite")
    got = harness.read_layer_metrics(cell, {"rounds": 27, "fits": 2},
                                     summary, chip)
    assert got["fit.stream_lsh_hash_ms"]["value"] == 4.0
    summary = summary._replace(ops={"roi_filter_pallas": (1e6, 1)})
    got = harness.read_layer_metrics(cell, {"rounds": 27, "fits": 1},
                                     summary, chip)
    assert "fit.stream_lsh_hash_ms" not in got
