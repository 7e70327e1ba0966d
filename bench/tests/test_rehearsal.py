"""CPU rehearsal of every cell: each driver runs end to end at a tiny size
and gives a result line of the right shape; the command itself refuses to
run, and prints no result, off the chip."""

import copy
import json
import os
import subprocess
import sys
import time

import jax
import pytest

import harness

TINY = {
    "blobs100k-d128": {"n": 2000, "d": 16, "clusters": 8, "data_seeds": [3, 4],
                       "lsh": {"seg_len": 57.37},
                       "alid": {"a_cap": 132, "delta": 32,
                                "max_rounds": 4, "seeds_per_round": 8},
                       "checks": {"planted_found": {"op": ">=", "limit": 1},
                                  "avg_f": {"op": ">=", "limit": 0.3}}},
    "blobs1m-d128": {"n": 4000, "d": 16, "clusters": 8, "cap": 232,
                     "checks": {"labelled_share": {"op": "in",
                                                   "limit": [0.45, 0.95]}}},
}


def tiny(cell: harness.Cell) -> harness.Cell:
    conf = copy.deepcopy(cell.config)
    for key, value in TINY[conf["name"]].items():
        if isinstance(value, dict):
            conf[key].update(value)
        else:
            conf[key] = value
    traffic = dict(cell.traffic)
    if "rate_hz" in traffic:
        traffic.update(rate_hz=min(traffic["rate_hz"], 200.0),
                       trace_seconds=0.5, check_sample=64)
    return cell._replace(config=conf, traffic=traffic)


CELLS = [w["name"] for w in json.load(open(os.path.join(
    harness.ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(name):
    cell = tiny(harness.load_cell(name))
    out = harness.run_cell(cell, seed=2**31 + 7, seconds=1.0, trace=False,
                           t_start=time.perf_counter(),
                           devices=jax.devices()[:1], log=lambda m: None)
    line = json.loads(json.dumps(out))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, harness.check_lines(line)
    assert line["attempted"] >= 1 and line["failed"] == 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(line["metrics"]) == want
    for m in cell.end_to_end:
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_refuses_without_device_trace(name):
    """Off the chip the trace holds no device plane: a traced run fails
    rather than report device metrics."""
    cell = tiny(harness.load_cell(name))
    with pytest.raises(ValueError, match="device plane"):
        harness.run_cell(cell, seed=3, seconds=1.0, trace=True,
                         t_start=time.perf_counter(),
                         devices=jax.devices()[:1], log=lambda m: None)


def test_command_refuses_off_chip(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert "{" not in proc.stdout
