"""The program's span/counter recorder (`repro.utils.trace`): it records
only under a profile, counts the peel loop's rounds and the serving
worker's batches exactly, leaves results unchanged, merges threads and
computes self time; and the counter classes share one base."""

import threading

import jax
import numpy as np
import pytest

from repro.core.alid import ALIDConfig, EngineSpec
from repro.core.engine import fit
from repro.core.online import OnlineStats
from repro.core.pipeline import PipelineStats
from repro.data import auto_lsh_params, make_blobs_with_noise
from repro.serve import ClusterServer, ServingStats
from repro.utils import trace


@pytest.fixture
def profile(tmp_path):
    """Records while the JAX profiler runs; the table starts empty."""
    trace.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@pytest.fixture
def forced(monkeypatch):
    """Records without a profiler (the annotations are then no-ops)."""
    trace.reset()
    monkeypatch.setattr(trace, "recording", lambda: True)


@pytest.fixture(scope="module")
def blobs():
    spec = make_blobs_with_noise(n_clusters=3, cluster_size=30, n_noise=60,
                                 d=8, seed=11, overlap_pairs=0)
    cfg = ALIDConfig(a_cap=48, delta=48,
                     lsh=auto_lsh_params(spec.points, probe=128),
                     seeds_per_round=16, max_rounds=16)
    return spec, cfg


@pytest.fixture(scope="module")
def fits(blobs, tmp_path_factory):
    """The same fit unrecorded and recorded, and the recorded table."""
    spec, cfg = blobs
    plain = fit(spec.points, cfg, jax.random.PRNGKey(0))
    trace.reset()
    jax.profiler.start_trace(str(tmp_path_factory.mktemp("fit_profile")))
    try:
        recorded = fit(spec.points, cfg, jax.random.PRNGKey(0))
    finally:
        jax.profiler.stop_trace()
    return plain, recorded, trace.summary()


def test_nothing_recorded_outside_a_profile():
    trace.reset()
    stats = ServingStats()
    with trace.span("outer", stats, "pack_s", meta=1) as sp:
        sp.annotate(more=2)
        with trace.span("inner"):
            pass
    trace.count("counter", 5)
    assert not trace.recording()
    assert trace.summary() == {}
    assert stats.pack_s > 0.0            # the counter field is fed anyway


def test_fit_records_every_round(fits):
    _, res, s = fits
    assert s["alid.fit"][0] == 1 and s["alid.build"][0] == 1
    assert s["alid.round"][0] == res.n_rounds
    assert s["alid.round.wait"][1] <= s["alid.round"][1]
    assert all(own >= 0 for _, _, own in s.values())
    assert (s["alid.lane_iters_useful"][0]
            <= s["alid.lane_iters_executed"][0])
    assert s["alid.clusters_accepted"][0] == res.n_clusters
    assert 0 < s["alid.seeds_valid"][0] <= 16 * res.n_rounds


@pytest.mark.parametrize("engine,probes", [
    ("replicated", "lsh.probes_directory"),
    ("sharded", "lsh.probes_searched"),
])
def test_fit_counts_its_probe_path(blobs, forced, engine, probes):
    """A recorded fit says which LSH probe ran: the replicated tables'
    directory or the shard tables' binary search, one count per support
    slot and table of every executed CIVS pass."""
    spec, cfg = blobs
    cfg = cfg._replace(spec=EngineSpec(
        engine=engine, n_shards=3 if engine == "sharded" else 0))
    fit(spec.points, cfg, jax.random.PRNGKey(0))
    s = trace.summary()
    other = ({"lsh.probes_directory", "lsh.probes_searched"} - {probes}).pop()
    assert other not in s
    assert s[probes][0] == (s["alid.lane_iters_executed"][0] * cfg.a_cap
                            * cfg.lsh.n_tables) > 0


def test_fit_results_identical_with_recording(fits):
    plain, recorded, _ = fits
    for name in ("labels", "densities", "support_idx", "support_w",
                 "support_v"):
        np.testing.assert_array_equal(getattr(plain, name),
                                      getattr(recorded, name))
    assert plain.n_rounds == recorded.n_rounds and plain.k == recorded.k


def test_server_records_every_batch(blobs, fits, profile):
    spec, _ = blobs
    plain, _, _ = fits
    server = ClusterServer(batch_slots=4, queue_limit=64, start=False)
    server.add_tenant("default", plain)
    futs = [server.submit(q) for q in spec.points[:10]]
    server.start()
    for f in futs:
        f.result(timeout=30)
    server.close()
    s, stats = trace.summary(), server.stats.snapshot()
    assert s["serve.batch"][0] == stats["batches"] == 3
    for stage in ("serve.pack", "serve.upload", "serve.launch", "serve.wait",
                  "serve.resolve"):
        assert s[stage][0] == 3
    compute_ns = sum(s[k][1] for k in ("serve.upload", "serve.launch",
                                        "serve.wait"))
    assert stats["compute_s"] == pytest.approx(compute_ns * 1e-9)
    assert stats["pack_s"] == pytest.approx(s["serve.pack"][1] * 1e-9)
    assert s["serve.batch"][2] >= 0


def test_threads_merge_exact_counts(forced):
    n_threads, n_spans = 8, 500
    start = threading.Barrier(n_threads)

    def work():
        start.wait(10)
        for _ in range(n_spans):
            with trace.span("t.outer"):
                with trace.span("t.inner"):
                    trace.count("t.count", 2)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not any(t.is_alive() for t in threads)
    s = trace.summary()
    assert s["t.outer"][0] == s["t.inner"][0] == n_threads * n_spans
    assert s["t.count"] == (2 * n_threads * n_spans, 0, 0)
    trace.reset()
    assert trace.summary() == {}


def test_nested_self_time_on_a_fake_clock(forced, monkeypatch):
    ticks = iter([0, 10, 40, 50, 60, 100])
    monkeypatch.setattr(trace, "_clock", lambda: next(ticks))
    with trace.span("outer"):           # 0 .. 100
        with trace.span("inner"):       # 10 .. 40
            pass
        with trace.span("inner"):       # 50 .. 60
            pass
    s = trace.summary()
    assert s["outer"] == (1, 100, 60)
    assert s["inner"] == (2, 40, 40)


@pytest.mark.parametrize("cls", [PipelineStats, ServingStats, OnlineStats])
def test_counter_classes_share_one_base(cls):
    stats = cls()
    assert isinstance(stats, trace.Counters)
    timed = [f for f in cls._FIELDS if f.endswith("_s")]
    counted = [f for f in cls._FIELDS if not f.endswith("_s")]
    for f in timed:
        stats.add(f, 0.25)
    for f in counted:
        stats.add(f)
        stats.add(f, 2)
    stats.peak(counted[0], 7)
    stats.peak(counted[0], 1)
    snap = stats.snapshot()
    assert list(snap) == list(cls._FIELDS)
    assert all(snap[f] == 0.25 and isinstance(snap[f], float) for f in timed)
    assert snap[counted[0]] == 7
    assert all(snap[f] == 3 and isinstance(snap[f], int)
               for f in counted[1:])
