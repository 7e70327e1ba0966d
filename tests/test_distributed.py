"""Distribution tests: PALID == serial ALID on a real (virtual-device) mesh;
mini dry-run on a small mesh; sharding-rule unit tests. Mesh tests run in
subprocesses because XLA_FLAGS must be set before jax initializes."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.mark.slow  # subprocess: re-imports jax on 8 virtual devices
def test_palid_matches_serial_alid():
    out = run_subprocess("""
        import jax, json
        import numpy as np
        from repro.data import make_blobs_with_noise, auto_lsh_params
        from repro.core.alid import ALIDConfig, detect_clusters
        from repro.core.palid import detect_clusters_parallel
        from repro.launch.mesh import make_small_context
        from repro.utils import avg_f1_score

        spec = make_blobs_with_noise(n_clusters=5, cluster_size=30, n_noise=100,
                                     d=12, seed=11)
        lshp = auto_lsh_params(spec.points)
        cfg = ALIDConfig(a_cap=48, delta=48, lsh=lshp, seeds_per_round=16,
                         max_rounds=20)
        ser = detect_clusters(spec.points, cfg, jax.random.PRNGKey(3))
        ctx = make_small_context(n_data=8, n_model=1)
        par = detect_clusters_parallel(spec.points, cfg, jax.random.PRNGKey(3),
                                       ctx)
        f_ser = avg_f1_score(spec.labels, ser.labels)
        f_par = avg_f1_score(spec.labels, par.labels)
        # same seeds, same math -> same clustering quality
        print(json.dumps({"f_ser": f_ser, "f_par": f_par,
                          "n_ser": len(ser.densities),
                          "n_par": len(par.densities)}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["f_par"] > 0.55, res
    assert abs(res["f_ser"] - res["f_par"]) < 0.15, res


@pytest.mark.slow  # subprocess dry-run: lowers+compiles two full archs
def test_mini_dryrun_small_mesh():
    """Lower+compile smoke configs for a 4x2 mesh through the real sharding
    machinery (the production-mesh equivalent runs in launch/dryrun.py)."""
    out = run_subprocess("""
        import jax, jax.numpy as jnp, functools
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_arch
        from repro.distributed.context import (MeshContext, make_mesh,
                                               mesh_context)
        from repro.distributed import shardings as shd
        from repro.models import transformer as lm_m
        from repro.train import steps as steps_lib
        from repro.train.optimizers import OptConfig, init_opt_state

        mesh = make_mesh((4, 2), ("data", "model"))
        ctx = MeshContext(mesh=mesh, data_axes=("data",), model_axis="model")
        for arch in ["gemma2-27b", "kimi-k2-1t-a32b"]:
            cfg = get_arch(arch).SMOKE_CONFIG
            with mesh_context(ctx):
                pa = lm_m.abstract_params(cfg)
                ps = shd.lm_param_specs(pa, cfg)
                nsh = jax.tree.map(lambda s: NamedSharding(mesh, s), ps,
                                   is_leaf=lambda s: isinstance(s, P))
                opt = OptConfig()
                oa = jax.eval_shape(functools.partial(init_opt_state, opt), pa)
                osp = shd.opt_state_specs(ps, pa, oa)
                osh = jax.tree.map(lambda s: NamedSharding(mesh, s), osp,
                                   is_leaf=lambda s: isinstance(s, P))
                fn = steps_lib.make_lm_train_step(cfg, opt, microbatches=2)
                toks = jax.ShapeDtypeStruct((8, 33), jnp.int32)
                c = jax.jit(fn, in_shardings=(nsh, osh,
                                              NamedSharding(mesh, P("data", None))),
                            out_shardings=(nsh, osh, None)
                            ).lower(pa, oa, toks).compile()
                ca = c.cost_analysis()
                ca = ca[0] if isinstance(ca, list) else ca  # jax 0.4.x: list
                print(arch, "compiled", ca["flops"] > 0)
    """)
    assert out.count("compiled True") == 2, out


@pytest.mark.slow  # subprocess dry-run: runs a sharded MoE step on 8 devices
def test_mini_dryrun_runs_real_arrays():
    """Not just compile: run a sharded MoE train step on 8 devices and check
    finite loss (exercises the shard_map all-to-alls for real)."""
    out = run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.configs import get_arch
        from repro.distributed.context import (MeshContext, make_mesh,
                                               mesh_context)
        from repro.train import steps as S
        from repro.train.optimizers import OptConfig

        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = MeshContext(mesh=mesh, data_axes=("data",), model_axis="model")
        cfg = get_arch("kimi-k2-1t-a32b").SMOKE_CONFIG
        opt = OptConfig(lr=1e-3)
        with mesh_context(ctx):
            params, opt_state = S.init_train_state(jax.random.PRNGKey(0), "lm",
                                                   cfg, opt)
            step = jax.jit(S.make_lm_train_step(cfg, opt, microbatches=2))
            toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab)
            with mesh:
                params, opt_state, m = step(params, opt_state, toks)
        import numpy as np
        assert np.isfinite(float(m["loss"])), m
        print("moe sharded step ok", float(m["loss"]))
    """)
    assert "moe sharded step ok" in out


def test_zero_shard_spec_rules():
    from repro.distributed.shardings import zero_shard_spec
    # no mesh context -> identity
    assert zero_shard_spec(P(None, "model"), (64, 32)) == P(None, "model")


def test_degrade_spec_without_ctx():
    from repro.distributed.shardings import degrade_spec
    assert degrade_spec(P("data"), (7,)) == P("data")  # no ctx -> unchanged


def test_collective_census_parsing():
    from repro.launch.dryrun import collective_census
    hlo = """
ENTRY %main (a: f32[8]) -> f32[8] {
  %ag = f32[1024,256]{1,0} all-gather(%x), replica_groups=[2,4]<=[8]
  %ar.1 = f32[512]{0} all-reduce-start(%y)
  %w = (f32[8]) while(%t), condition=%cond, body=%wbody, backend_config={"known_trip_count":{"n":"10"}}
}
%wbody (p: f32[8]) -> f32[8] {
  %rs = bf16[128,64]{1,0} reduce-scatter(%z)
}
"""
    c = collective_census(hlo)
    assert c["all-gather"]["bytes"] == 1024 * 256 * 4
    assert c["all-reduce"]["bytes"] == 512 * 4 * 2
    assert c["reduce-scatter"]["count"] == 10
    assert c["reduce-scatter"]["bytes"] == 128 * 64 * 2 * 10
