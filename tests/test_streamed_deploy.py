"""The out-of-core deployment at a small size: a streamed fit from a
memmapped `.npy`, with and without the host shard cache and the prefetch
ring, held to a plain float64 reference computed here; and the streamed
outer loop's spans and counters, recorded only while recording."""

import jax
import numpy as np
import pytest

from repro.core.alid import ALIDConfig, EngineSpec
from repro.core.engine import fit
from repro.core.source import MemmapSource
from repro.data import auto_lsh_params, make_blobs_with_noise
from repro.utils import trace

N_SHARDS = 4
KEY = 7


@pytest.fixture(scope="module")
def blobs():
    # n = 3,000 at d = 16: 8 clusters of 150 points (40%) in uniform noise
    return make_blobs_with_noise(n_clusters=8, cluster_size=150,
                                 n_noise=1800, d=16, seed=5)


@pytest.fixture(scope="module")
def npy(blobs, tmp_path_factory):
    path = tmp_path_factory.mktemp("collection") / "points.npy"
    np.save(path, blobs.points)
    return str(path)


@pytest.fixture(scope="module")
def cfg(blobs):
    return ALIDConfig(a_cap=182, delta=32, seeds_per_round=8, max_rounds=4,
                      lsh=auto_lsh_params(blobs.points, probe=16))


@pytest.fixture(scope="module")
def replicated(blobs, cfg):
    return fit(blobs.points, cfg, jax.random.PRNGKey(KEY))


def streamed(cfg, tmp_path, **spec):
    return cfg._replace(spec=EngineSpec(
        engine="streamed", n_shards=N_SHARDS, scratch_dir=str(tmp_path),
        **spec))


def best_f1(planted, labels):
    """For each planted cluster, the best F1 over the found clusters."""
    found = [c for c in np.unique(labels) if c >= 0]
    out = np.zeros(planted.max() + 1)
    for t in range(out.size):
        truth = planted == t
        for c in found:
            pred = labels == c
            both = np.sum(truth & pred)
            out[t] = max(out[t], 2.0 * both / (truth.sum() + pred.sum()))
    return out


def densities_f64(points, support_idx, support_w, k):
    """w^T A w of each cluster, A_ij = exp(-k ||v_i - v_j||) off the
    diagonal, from the points at its support indices."""
    out = []
    for idx, w in zip(support_idx, support_w):
        live = idx >= 0
        v = points[idx[live]].astype(np.float64)
        w = w[live].astype(np.float64)
        a = np.exp(-k * np.sqrt(((v[:, None] - v[None]) ** 2).sum(-1)))
        np.fill_diagonal(a, 0.0)
        out.append(w @ a @ w)
    return np.asarray(out)


@pytest.mark.parametrize("cache_bytes", [0, None], ids=["no-cache",
                                                       "default-cache"])
@pytest.mark.parametrize("prefetch_depth", [0, 2])
def test_memmapped_fit_holds_to_the_float64_reference(
        blobs, npy, cfg, replicated, tmp_path, cache_bytes, prefetch_depth):
    spec = {"prefetch_depth": prefetch_depth}
    if cache_bytes is not None:
        spec["cache_bytes"] = cache_bytes
    res = fit(MemmapSource(npy), streamed(cfg, tmp_path, **spec),
              jax.random.PRNGKey(KEY))
    points = blobs.points
    assert res.n_clusters > 0
    ref = densities_f64(points, res.support_idx, res.support_w, res.k)
    gap = np.abs(np.asarray(res.densities, np.float64) - ref) / ref
    assert gap.max() <= 2e-5
    # every labelled point lies in the support of its cluster
    lab = np.flatnonzero(res.labels >= 0)
    assert (res.support_idx[res.labels[lab]] == lab[:, None]).any(1).all()
    found = int((best_f1(blobs.labels, res.labels) >= 0.5).sum())
    assert found >= 1
    assert found >= int((best_f1(blobs.labels, replicated.labels)
                         >= 0.5).sum())


@pytest.mark.parametrize("recording", [True, False])
def test_streamed_fit_counts_its_shards(npy, cfg, tmp_path, monkeypatch,
                                        recording):
    """A recorded streamed fit counts, per outer iteration, the shards it
    routes against the store's shards and the (lane, shard) pairs routing
    keeps, and records its iterations; unrecorded, nothing moves."""
    trace.reset()
    if recording:
        monkeypatch.setattr(trace, "recording", lambda: True)
    fit(MemmapSource(npy), streamed(cfg, tmp_path, cache_bytes=0),
        jax.random.PRNGKey(KEY))
    s = trace.summary()
    trace.reset()
    if not recording:
        assert s == {}
        return
    iters = s["alid.stream.iter"][0]
    routed = s["alid.stream.shards_routed"][0]
    total = s["alid.stream.shards_total"][0]
    assert iters > 0 and total == iters * N_SHARDS
    assert 0 < routed <= total
    assert routed <= s["alid.stream.lane_shard_pairs"][0] <= (
        routed * cfg.seeds_per_round)
    assert s["alid.stream.route"][0] == iters
    assert s["alid.stream.wait"][0] >= 3 * iters
    assert s["alid.stream.wait"][1] <= s["alid.stream.iter"][1]
    assert s["alid.stream.chunk"][0] == routed


class _NoFetch:
    """A device array stand-in that refuses to be copied to the host."""
    shape = (3, 16)

    def __array__(self, *args, **kwargs):
        raise AssertionError("the ROI centers were copied to the host")


def test_route_off_the_l2_metric_routes_every_shard_unfetched():
    """Ball routing holds only for p = 2; any other metric routes every
    shard of every lane without copying the ROI off the device."""
    from types import SimpleNamespace

    from repro.core.engine import StreamedEngine
    eng = SimpleNamespace(_store=SimpleNamespace(n_shards=N_SHARDS))
    roi = SimpleNamespace(center=_NoFetch(), radius=_NoFetch())
    touch = StreamedEngine._route(eng, roi, 1.0)
    assert touch.shape == (3, N_SHARDS) and touch.all()
