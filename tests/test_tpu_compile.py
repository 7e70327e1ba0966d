"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode runs a kernel as jax ops, so it cannot see what the chip's
compiler (Mosaic) refuses: stores of scalars to VMEM, 1-D vector layouts,
more scoped VMEM than a kernel may use. These tests lower each kernel at
SIFT width (d = 128: LID capacity 2160, 200 clusters of 2032-slot
supports) for one chip of a `v5e:2x2` topology described without a chip
attached, and check the compiled program holds the kernel
(`tpu_custom_call`). One more test compiles a whole PALID map round over
the four described chips with the mesh-placed store, where GSPMD would
have to partition the kernels and cannot. Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.alid import ALIDConfig, EngineSpec
from repro.core.engine import _map_round_mesh
from repro.core.store import build_store
from repro.distributed.context import MeshContext
from repro.distributed.shardings import store_specs
from repro.kernels import ops
from repro.lsh.pstable import LSHParams

D = 128
CAP = 2160                  # a_cap 2032 + delta 128
SEEDS = 32                  # seeds_per_round: the engine vmaps the sweep
CLUSTERS, A_CAP = 200, 2032


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001 - reported
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_lid_sweep_compiles(one_chip, dtype):
    def sweep(v, idx, mask, x, ax, it, cv, k):
        return ops.lid_sweep(v, idx, mask, x, ax, it, cv, k, n_steps=8,
                             max_iters=200, tol=1e-5, backend="pallas")

    batched = jax.vmap(sweep, in_axes=(0,) * 7 + (None,))
    text = _compiled_text(
        batched, one_chip,
        ((SEEDS, CAP, D), dtype), ((SEEDS, CAP), jnp.int32),
        ((SEEDS, CAP), jnp.bool_), ((SEEDS, CAP), jnp.float32),
        ((SEEDS, CAP), jnp.float32), ((SEEDS,), jnp.int32),
        ((SEEDS,), jnp.bool_), ((), jnp.float32))
    assert "tpu_custom_call" in text


def test_assign_compiles(one_chip):
    def assign(q, sup_v, sup_w, dens, k, thr):
        return ops.assign_clusters(q, sup_v, sup_w, dens, k, thr,
                                   backend="pallas")

    text = _compiled_text(
        assign, one_chip,
        ((256, D), jnp.float32), ((CLUSTERS, A_CAP, D), jnp.float32),
        ((CLUSTERS, A_CAP), jnp.float32), ((CLUSTERS,), jnp.float32),
        ((), jnp.float32), ((), jnp.float32))
    assert "tpu_custom_call" in text


def test_roi_filter_compiles(one_chip):
    def roi(vc, center, radius, valid):
        return ops.roi_filter(vc, center, radius, valid, backend="pallas")

    text = _compiled_text(roi, one_chip, ((4096, D), jnp.float32),
                          ((D,), jnp.float32), ((), jnp.float32),
                          ((4096,), jnp.bool_))
    assert "tpu_custom_call" in text


def test_affinity_matvec_compiles(one_chip):
    def matvec(q, qi, c, ci, w, k):
        return ops.affinity_matvec(q, qi, c, ci, w, k, backend="pallas")

    text = _compiled_text(matvec, one_chip, ((CAP, D), jnp.float32),
                          ((CAP,), jnp.int32), ((A_CAP, D), jnp.float32),
                          ((A_CAP,), jnp.int32), ((A_CAP,), jnp.float32),
                          ((), jnp.float32))
    assert "tpu_custom_call" in text


def test_lsh_hash_compiles(one_chip):
    def lsh(x, proj, bias):
        return ops.lsh_hash(x, proj, bias, 4.0, backend="pallas")

    text = _compiled_text(lsh, one_chip, ((65536, D), jnp.float32),
                          ((4, 8, D), jnp.float32), ((4, 8), jnp.float32))
    assert "tpu_custom_call" in text


def test_affinity_compiles(one_chip):
    def aff(q, c, k):
        return ops.affinity(q, c, k, backend="pallas")

    text = _compiled_text(aff, one_chip, ((512, D), jnp.float32),
                          ((CAP, D), jnp.float32), ((), jnp.float32))
    assert "tpu_custom_call" in text


def test_mesh_placed_store_round_compiles(topo):
    """One PALID map round on four chips against the device-placed
    ShardedStore (`MeshEngine` with n_shards=4), at the smoke's n = 30,000."""
    n, n_shards = 30_000, 4
    mesh = Mesh(np.array(topo.devices), ("data",))
    ctx = MeshContext(mesh=mesh, data_axes=("data",), model_axis="data")
    lsh = LSHParams(seg_len=275.0)
    cfg = ALIDConfig(a_cap=92, delta=128, lsh=lsh, seeds_per_round=32,
                     spec=EngineSpec(engine="mesh", n_shards=n_shards,
                                     mesh_ctx=ctx, backend="pallas"))
    store = jax.eval_shape(
        lambda v: build_store(v, lsh, jax.random.PRNGKey(0),
                              n_shards=n_shards, backend="ref"),
        jax.ShapeDtypeStruct((n, D), jnp.float32))
    store = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        store, store_specs(store), is_leaf=lambda s: isinstance(s, P))
    rep = NamedSharding(mesh, P())
    text = _map_round_mesh.lower(
        store, jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=rep), None,
        jax.ShapeDtypeStruct((32,), jnp.int32,
                             sharding=NamedSharding(mesh, P("data"))),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=rep), cfg, ctx,
    ).compile().as_text()
    assert "tpu_custom_call" in text
