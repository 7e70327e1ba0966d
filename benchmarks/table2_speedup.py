"""Paper Table 2: PALID speedup with executors. The paper reports 7.51x with
8 Spark executors on SIFT-50M.

Runs in-process over the devices JAX sees (`jax.devices()`): one fit on the
replicated engine, then PALID on a mesh of the first 2/4/8 devices, for
each count the process has. On a CPU host, expose virtual devices with
`XLA_FLAGS=--xla_force_host_platform_device_count=N` before JAX starts;
they share the host's cores, so their walltime cannot show real speedup.
Each row reports (a) the exact per-device work partition (seeds per device
— the quantity that scales on real chips) and (b) walltime, naming the
device it ran on."""

from __future__ import annotations

import time

import jax

from benchmarks.common import csv_line
from repro.core.alid import ALIDConfig, EngineSpec
from repro.core.engine import fit
from repro.data import auto_lsh_params, make_blobs_with_noise
from repro.distributed.context import MeshContext, make_mesh
from repro.utils import avg_f1_score


def main(quick: bool = True):
    devices = jax.devices()
    counts = [c for c in ((1, 4) if quick else (1, 2, 4, 8))
              if c <= len(devices)]
    spec = make_blobs_with_noise(n_clusters=10, cluster_size=60, n_noise=2000,
                                 d=16, seed=9)
    kind = devices[0].device_kind
    rows = []
    for dev in counts:
        if dev > 1:
            mesh = make_mesh((dev,), ("data",), devices=devices[:dev])
            ctx = MeshContext(mesh=mesh, data_axes=("data",),
                              model_axis="data")
            espec = EngineSpec(engine="mesh", mesh_ctx=ctx)
        else:
            espec = EngineSpec(engine="replicated")
        cfg = ALIDConfig(a_cap=128, delta=128,
                         lsh=auto_lsh_params(spec.points),
                         seeds_per_round=32, max_rounds=24, spec=espec)
        t0 = time.perf_counter()
        res = fit(spec.points, cfg, jax.random.PRNGKey(0))
        rec = dict(devices=dev, wall_s=time.perf_counter() - t0,
                   seeds_per_device=cfg.seeds_per_round // dev,
                   avgf=avg_f1_score(spec.labels, res.labels),
                   rounds=res.n_rounds)
        rows.append(rec)
        work_ratio = rows[0]["seeds_per_device"] / rec["seeds_per_device"]
        csv_line(f"table2/palid_{dev}exec", rec["wall_s"] * 1e6,
                 f"work_partition_speedup={work_ratio:.2f};"
                 f"avgf={rec['avgf']:.3f};wall_s={rec['wall_s']:.1f};"
                 f"device={kind}")
    return rows


if __name__ == "__main__":
    main(quick=False)
