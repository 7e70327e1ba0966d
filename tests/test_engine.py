"""The unified ClusterEngine API: engine parity, the shared claim reducer
(deliberate ties), Clustering.predict / serialization, and the deprecation
shims over the old entry points.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.alid import (ALIDConfig, Clustering, EngineSpec,
                             detect_clusters, detect_clusters_sharded)
from repro.core.engine import fit, make_engine, resolve_claims
from repro.core.palid import detect_clusters_parallel
from repro.data import auto_lsh_params, make_blobs_with_noise
from repro.distributed.context import MeshContext, make_mesh
from repro.utils import avg_f1_score, canonical_labels as canonical


@pytest.fixture(scope="module")
def blobs():
    # well-separated blobs: detected clusters coincide with true ones, so
    # the predict round-trip is unambiguous
    return make_blobs_with_noise(n_clusters=4, cluster_size=25, n_noise=80,
                                 d=10, seed=7, overlap_pairs=0)


@pytest.fixture(scope="module")
def cfg(blobs):
    # probe >= max bucket size -> retrieval is exhaustive and tie-free data
    # makes all engines bit-compatible (DESIGN.md §3.1)
    lshp = auto_lsh_params(blobs.points, probe=128)
    return ALIDConfig(a_cap=48, delta=48, lsh=lshp, seeds_per_round=16,
                      max_rounds=20)


_SPECS = {
    "replicated": EngineSpec(engine="replicated"),
    "sharded": EngineSpec(engine="sharded", n_shards=5),
    "mesh": EngineSpec(engine="mesh"),
    "mesh_sharded": EngineSpec(engine="mesh", n_shards=4),
    # host-streamed: store built from source chunks (odd chunk_size on
    # purpose — chunking must not change anything), CIVS driven one
    # device_put shard at a time
    "streamed": EngineSpec(engine="streamed", n_shards=5, chunk_size=37),
}


@pytest.fixture(scope="module")
def reference(blobs, cfg):
    """Replicated-engine clustering per exhaustive mode (parity baseline)."""
    out = {}
    for exhaustive in (False, True):
        out[exhaustive] = fit(
            blobs.points, cfg._replace(exhaustive=exhaustive),
            jax.random.PRNGKey(0))
    return out


@pytest.mark.parametrize("exhaustive", [False, True])
@pytest.mark.parametrize("engine", ["replicated", "sharded", "mesh",
                                    "mesh_sharded", "streamed"])
def test_engine_parity(blobs, cfg, reference, engine, exhaustive):
    """The tentpole acceptance: every EngineSpec yields identical labels on
    tie-free data — same rng stream, same seeding statistics, exact
    retrieval parity, one shared reducer. n_rounds equality doubles as the
    rng-consumption check (one split per round, all engines in lockstep)."""
    ref = reference[exhaustive]
    res = fit(blobs.points,
              cfg._replace(exhaustive=exhaustive, spec=_SPECS[engine]),
              jax.random.PRNGKey(0))
    assert ref.n_clusters > 0
    np.testing.assert_array_equal(canonical(ref.labels), canonical(res.labels))
    np.testing.assert_allclose(np.sort(ref.densities), np.sort(res.densities),
                               rtol=1e-6)
    assert res.n_rounds == ref.n_rounds


def test_make_engine_rejects_unknown():
    with pytest.raises(ValueError, match="unknown engine"):
        make_engine(EngineSpec(engine="quantum"))


# --------------------------------------------------- kernel-backend parity --
@pytest.fixture(scope="module")
def small_blobs():
    """Tiny, well-separated set: keeps the interpret-mode fits fast."""
    return make_blobs_with_noise(n_clusters=3, cluster_size=16, n_noise=40,
                                 d=8, seed=3, overlap_pairs=0)


@pytest.mark.parametrize("engine,kw", [
    ("replicated", {}),
    ("sharded", dict(n_shards=4)),
    ("streamed", dict(n_shards=4, chunk_size=23)),
])
def test_backend_interpret_parity(small_blobs, engine, kw):
    """Tentpole acceptance: the Pallas kernels (interpret mode — the same
    kernel code the TPU compiles, executed as jax ops) must yield labels
    BIT-IDENTICAL to the pure-jnp reference backend, per engine. Every
    hot-path op (affinity columns, Ax refresh matvec, ROI filter, LSH keys,
    probe hashing) runs through `repro.kernels.ops` on both sides; any
    private compute sneaking back into lid/civs/pstable would break this."""
    lshp = auto_lsh_params(small_blobs.points, probe=64)
    cfg = ALIDConfig(a_cap=24, delta=24, lsh=lshp, seeds_per_round=8,
                     max_rounds=10, t_lid=128)
    res = {}
    for backend in ("ref", "interpret"):
        spec = EngineSpec(engine=engine, backend=backend, **kw)
        res[backend] = fit(small_blobs.points, cfg._replace(spec=spec),
                           jax.random.PRNGKey(0))
    assert res["ref"].n_clusters > 0
    np.testing.assert_array_equal(res["ref"].labels,
                                  res["interpret"].labels)
    np.testing.assert_array_equal(res["ref"].densities,
                                  res["interpret"].densities)
    assert res["ref"].n_rounds == res["interpret"].n_rounds


def test_replicated_fit_same_through_directory_or_search(blobs,
                                                         monkeypatch):
    """The replicated CIVS pass reads its support rows' buckets from the
    tables' directory; searching them instead (`query_batch` on the same
    rows, which hashes them and computes their salts itself) must give the
    same fit. probe = 4 is below the largest bucket, so the salts place
    the windows."""
    import repro.core.civs as civs
    from repro.lsh.pstable import query_batch

    lshp = auto_lsh_params(blobs.points, probe=4)
    cfg = ALIDConfig(a_cap=48, delta=48, lsh=lshp, seeds_per_round=16,
                     max_rounds=20)
    pts = jnp.asarray(blobs.points)

    def searched(tables, idx, salts, probe):
        return query_batch(tables, pts[jnp.clip(idx, 0, pts.shape[0] - 1)],
                           lshp)

    engine = make_engine(cfg.spec)
    direct = fit(blobs.points, cfg, jax.random.PRNGKey(0), engine=engine)
    assert int(np.asarray(engine.bucket_sizes).max()) > lshp.probe
    jax.clear_caches()              # retrace CIVS with the searched probe
    monkeypatch.setattr(civs, "probe_rows", searched)
    try:
        search = fit(blobs.points, cfg, jax.random.PRNGKey(0))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert direct.n_clusters > 0 and direct.n_rounds == search.n_rounds
    for name in ("labels", "densities", "support_idx", "support_w",
                 "support_v"):
        np.testing.assert_array_equal(getattr(direct, name),
                                      getattr(search, name))


# ------------------------------------------------------- the claim reducer --
def test_reducer_exact_tie_prefers_larger_row():
    """Deliberate exact density tie: the point claimed by both rows must go
    to the LARGER row id, deterministically (the segment-max tie-break every
    engine shares; the old palid host loop could disagree here)."""
    member_idx = jnp.array([[0, 1, 2, -1], [2, 3, 4, -1]], jnp.int32)
    member_mask = member_idx >= 0
    dens = jnp.array([0.9, 0.9], jnp.float32)          # exact tie
    seed_valid = jnp.array([True, True])
    claimed, best_row, _ = resolve_claims(member_idx, member_mask, dens,
                                          seed_valid, n=6)
    row = np.asarray(best_row)
    assert row[2] == 1, "tie must break toward the larger seed row id"
    assert row[0] == 0 and row[1] == 0 and row[3] == 1 and row[4] == 1
    assert not bool(np.asarray(claimed)[5])


def test_reducer_respects_density_and_validity():
    member_idx = jnp.array([[0, 1], [0, 1], [0, 1]], jnp.int32)
    member_mask = jnp.ones_like(member_idx, bool)
    dens = jnp.array([0.5, 0.8, 0.9], jnp.float32)
    seed_valid = jnp.array([True, True, False])        # row 2 never claims
    _, best_row, _ = resolve_claims(member_idx, member_mask, dens,
                                    seed_valid, n=2)
    assert (np.asarray(best_row) == 1).all()


@pytest.mark.parametrize("engine", ["replicated", "mesh"])
def test_tied_data_serial_vs_mesh(engine, cfg):
    """End-to-end deliberate ties: duplicated points make seed instances
    converge to bitwise-identical densities; with ONE shared reducer the
    serial and mesh engines must still agree label-for-label."""
    rng = np.random.default_rng(1)
    blob = rng.normal(0, 0.5, size=(20, 6)).astype(np.float32)
    far = rng.normal(20, 0.5, size=(20, 6)).astype(np.float32)
    noise = rng.uniform(-40, 40, size=(60, 6)).astype(np.float32)
    pts = np.concatenate([blob, blob, far, noise])     # exact duplicates
    tie_cfg = ALIDConfig(a_cap=64, delta=48,
                         lsh=auto_lsh_params(pts, probe=128),
                         seeds_per_round=16, max_rounds=16)
    ref = fit(pts, tie_cfg, jax.random.PRNGKey(0))
    res = fit(pts, tie_cfg._replace(spec=_SPECS[engine]),
              jax.random.PRNGKey(0))
    np.testing.assert_array_equal(ref.labels, res.labels)


# ------------------------------------------------- Clustering as an object --
def test_predict_round_trip(blobs, cfg, reference):
    res = reference[False]
    assert res.n_clusters > 0
    for c in range(res.n_clusters):
        members = blobs.points[res.labels == c]
        np.testing.assert_array_equal(res.predict(members),
                                      np.full(len(members), c))
    far = blobs.points[:16] + 100.0                    # far from every cluster
    np.testing.assert_array_equal(res.predict(far), np.full(16, -1))


def test_predict_without_supports_is_noise():
    empty = Clustering(labels=np.full(4, -1, np.int32),
                       densities=np.zeros(0, np.float32), n_rounds=0, k=1.0)
    np.testing.assert_array_equal(empty.predict(np.zeros((3, 5))),
                                  np.full(3, -1))


def test_serialization_round_trip(tmp_path, blobs, reference):
    res = reference[False]
    path = tmp_path / "clustering.npz"
    res.save(path)
    loaded = Clustering.load(path)
    np.testing.assert_array_equal(loaded.labels, res.labels)
    np.testing.assert_allclose(loaded.densities, res.densities)
    assert loaded.n_rounds == res.n_rounds and loaded.k == res.k
    # predictions survive the round trip (supports carried in the file)
    q = blobs.points[:32]
    np.testing.assert_array_equal(loaded.predict(q), res.predict(q))
    # NumPy-safe: every serialized field is a plain numpy array
    for v in loaded.to_dict().values():
        assert not isinstance(v, jax.Array)


# ------------------------------------------------------- deprecation shims --
def test_detect_clusters_shims_warn_and_match(blobs, cfg, reference):
    with pytest.warns(DeprecationWarning, match="detect_clusters is"):
        ser = detect_clusters(blobs.points, cfg, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(ser.labels, reference[False].labels)

    with pytest.warns(DeprecationWarning, match="detect_clusters_sharded"):
        shd = detect_clusters_sharded(blobs.points, cfg, jax.random.PRNGKey(0),
                                      n_shards=5)
    np.testing.assert_array_equal(canonical(ser.labels), canonical(shd.labels))


def test_detect_clusters_parallel_shim_and_k_deprecation(blobs, cfg,
                                                         reference):
    mesh = make_mesh((jax.device_count(),), ("data",))
    ctx = MeshContext(mesh=mesh, data_axes=("data",), model_axis="data")
    with pytest.warns(DeprecationWarning, match="detect_clusters_parallel"):
        par = detect_clusters_parallel(blobs.points, cfg,
                                       jax.random.PRNGKey(0), ctx)
    np.testing.assert_array_equal(canonical(par.labels),
                                  canonical(reference[False].labels))
    # the redundant k= parameter fires its own warning and is honored
    with pytest.warns(DeprecationWarning, match="k= parameter"):
        res = detect_clusters_parallel(blobs.points, cfg,
                                       jax.random.PRNGKey(0), ctx,
                                       k=reference[False].k)
    assert res.k == pytest.approx(reference[False].k)


def test_fit_quality(blobs, cfg, reference):
    assert avg_f1_score(blobs.labels, reference[False].labels) > 0.8
