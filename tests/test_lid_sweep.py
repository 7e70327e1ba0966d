"""The fused multi-iteration LID sweep (`ops.lid_sweep`) contracts:

- interpret mode (the Pallas kernel as jax ops) bit-matches the jnp ref
  oracle, with and without the in-sweep Ax refresh, unbatched and vmapped;
- `lid_solve`'s while-over-chunks is bit-identical to the historical
  single-step loop (`lid_solve_unfused`) for any sweep_steps, and chunk
  granularity itself is bit-neutral at the op level;
- bf16 STORAGE with f32 accumulators converges to the same support set as
  f32 storage with tolerance-bounded densities;
- all three host engines agree bit-for-bit under backend="interpret" with
  the fused sweep on and bf16 storage.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lid
from repro.core.alid import ALIDConfig, EngineSpec
from repro.core.engine import fit
from repro.data import auto_lsh_params, make_blobs_with_noise
from repro.kernels import ops

CAP, D = 48, 16
K = jnp.float32(0.45)


def _live_state(seed: int = 0, dtype=jnp.float32) -> lid.LIDState:
    """A full-range LID state with a refreshed (non-stale) Ax, so the solver
    actually iterates instead of detecting convergence at step 0."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(4, D)) * 3.0
    pts = np.concatenate(
        [c + rng.normal(size=(CAP // 4, D)) for c in centers])
    v = jnp.asarray(pts, jnp.float32).astype(dtype)
    st = lid.init_state(v, jnp.int32(0), CAP)._replace(
        beta_idx=jnp.arange(CAP, dtype=jnp.int32),
        beta_mask=jnp.ones(CAP, bool),
        v_beta=v)
    return lid.refresh_ax(st, K, backend="ref")


def _sweep(st, backend, n_steps=8, max_iters=64, refresh_every=0):
    return ops.lid_sweep(st.v_beta, st.beta_idx, st.beta_mask, st.x, st.ax,
                         st.n_iters, st.converged, K, n_steps=n_steps,
                         max_iters=max_iters, tol=1e-5,
                         refresh_every=refresh_every, backend=backend)


# ------------------------------------------------ interpret vs ref parity --
@pytest.mark.parametrize("refresh_every", [0, 2])
def test_sweep_interpret_matches_ref(refresh_every):
    """The kernel executed as jax ops must reproduce the oracle bit-for-bit,
    including the optional every-M in-VMEM Ax refresh branch."""
    st = _live_state()
    got = _sweep(st, "interpret", refresh_every=refresh_every)
    want = _sweep(st, "ref", refresh_every=refresh_every)
    assert int(want[2]) > 1, "state did not iterate — test is vacuous"
    for g, w, name in zip(got, want, ("x", "ax", "n_iters", "converged")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"lid_sweep {name} diverged")


def test_sweep_vmap_interpret_matches_ref():
    """Batched seeds (the engine hot path): vmap over the sweep must keep
    interpret/ref parity per lane."""
    sts = jax.tree.map(lambda *xs: jnp.stack(xs),
                       *[_live_state(s) for s in range(4)])
    f = {b: jax.jit(jax.vmap(lambda s, b=b: _sweep(s, b)))
         for b in ("ref", "interpret")}
    got, want = f["interpret"](sts), f["ref"](sts)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_sweep_converged_state_is_noop():
    """A sweep entered with converged=True must return its inputs bit-
    unchanged and burn no iterations (the O(1)-final-iteration contract)."""
    st = _live_state()
    done = lid.lid_solve(st, K, max_iters=200, backend="ref")
    again = _sweep(done, "ref")
    np.testing.assert_array_equal(np.asarray(again[0]), np.asarray(done.x))
    np.testing.assert_array_equal(np.asarray(again[1]), np.asarray(done.ax))
    assert int(again[2]) == int(done.n_iters)
    assert bool(again[3])


# ----------------------------------------------------- chunked-solve parity --
@pytest.mark.parametrize("sweep_steps", [1, 3, 8, 200])
def test_chunked_solve_matches_unfused(sweep_steps):
    """while-over-sweeps == the historical per-iteration while_loop, bit for
    bit, regardless of chunk size (the sweep's per-step guard is the same
    predicate the outer loop re-checks)."""
    st = _live_state()
    got = lid.lid_solve(st, K, max_iters=200, sweep_steps=sweep_steps,
                        backend="ref")
    want = lid.lid_solve_unfused(st, K, max_iters=200)
    assert int(want.n_iters) > 2
    np.testing.assert_array_equal(np.asarray(got.x), np.asarray(want.x))
    np.testing.assert_array_equal(np.asarray(got.ax), np.asarray(want.ax))
    assert int(got.n_iters) == int(want.n_iters)
    assert bool(got.converged) == bool(want.converged)


def test_op_level_chunking_bit_neutral():
    """One n_steps=8 sweep == eight n_steps=1 sweeps with state threaded
    through the host (the benchmark's unfused arm), bitwise."""
    st = _live_state()
    one = _sweep(st, "ref", n_steps=8, max_iters=8)
    x, ax, it, cv = st.x, st.ax, st.n_iters, st.converged
    for _ in range(8):
        x, ax, it, cv = ops.lid_sweep(
            st.v_beta, st.beta_idx, st.beta_mask, x, ax, it, cv, K,
            n_steps=1, max_iters=8, tol=1e-5, backend="ref")
    for a, b in zip(one, (x, ax, it, cv)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_max_iters_is_cumulative_across_sweeps():
    """n_iters threads THROUGH chunk boundaries: a second sweep sees the
    budget already spent and stops at max_iters exactly."""
    st = _live_state()
    x, ax, it, cv = _sweep(st, "ref", n_steps=8, max_iters=10)
    assert int(it) == 8 and not bool(cv)
    x, ax, it, cv = ops.lid_sweep(
        st.v_beta, st.beta_idx, st.beta_mask, x, ax, it, cv, K,
        n_steps=8, max_iters=10, tol=1e-5, backend="ref")
    assert int(it) == 10


# ------------------------------------------------------- bf16 storage path --
def test_bf16_storage_matches_f32_support():
    """bf16 v_beta storage (f32 accumulators) must find the SAME support set
    as f32 storage; densities agree to bf16-rounding tolerance."""
    st32 = _live_state(dtype=jnp.float32)
    st16 = _live_state(dtype=jnp.bfloat16)
    assert st16.v_beta.dtype == jnp.bfloat16
    r32 = lid.lid_solve(st32, K, max_iters=200, backend="ref")
    r16 = lid.lid_solve(st16, K, max_iters=200, backend="ref")
    assert r16.x.dtype == jnp.float32 and r16.ax.dtype == jnp.float32
    sup32 = np.asarray(r32.beta_mask & (r32.x > 1e-6))
    sup16 = np.asarray(r16.beta_mask & (r16.x > 1e-6))
    np.testing.assert_array_equal(sup16, sup32)
    np.testing.assert_allclose(float(lid.density(r16)),
                               float(lid.density(r32)), rtol=5e-3)


def test_bf16_sweep_interpret_matches_ref():
    """Mixed-precision kernel parity: the upcast-once-then-f32 contract must
    hold identically in interpret mode and the ref oracle."""
    st = _live_state(dtype=jnp.bfloat16)
    got = _sweep(st, "interpret")
    want = _sweep(st, "ref")
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -------------------------------------------------------- engine parity -----
def test_bf16_engine_parity_interpret():
    """All host engines, backend="interpret" (Pallas kernel code as jax
    ops), dtype="bfloat16", fused sweep on: labels and densities must be
    bit-identical across engines — storage rounding happens once, BEFORE
    hashing, so every engine sees the same keys and the same LID inputs."""
    blobs = make_blobs_with_noise(n_clusters=3, cluster_size=16, n_noise=40,
                                  d=8, seed=3, overlap_pairs=0)
    lshp = auto_lsh_params(blobs.points, probe=64)
    cfg = ALIDConfig(a_cap=24, delta=24, lsh=lshp, seeds_per_round=8,
                     max_rounds=10, t_lid=128)
    res = {}
    for engine, kw in [("replicated", {}), ("sharded", dict(n_shards=4)),
                       ("streamed", dict(n_shards=4, chunk_size=23))]:
        spec = EngineSpec(engine=engine, backend="interpret",
                          dtype="bfloat16", **kw)
        res[engine] = fit(blobs.points, cfg._replace(spec=spec),
                          jax.random.PRNGKey(0))
    ref = res["replicated"]
    assert ref.n_clusters > 0
    for engine in ("sharded", "streamed"):
        np.testing.assert_array_equal(ref.labels, res[engine].labels)
        np.testing.assert_array_equal(ref.densities, res[engine].densities)
        assert res[engine].n_rounds == ref.n_rounds
