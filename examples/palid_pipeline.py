"""End-to-end PALID driver (the paper's SIFT-50M scenario, scaled to CPU):
build LSH index -> parallel seed rounds over a device mesh -> shared
segment-max reduce -> report clusters + quality, all through the unified
engine facade (`repro.core.engine.fit` with EngineSpec(engine="mesh")).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
        python examples/palid_pipeline.py --n 30000 --devices 8
"""

import argparse
import time

import jax
import numpy as np

from repro.core.alid import ALIDConfig, EngineSpec
from repro.core.engine import fit
from repro.data import auto_lsh_params, make_blobs_with_noise
from repro.distributed.context import MeshContext, make_mesh
from repro.utils import avg_f1_score


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=30000)
    ap.add_argument("--d", type=int, default=32, help="SIFT-like descriptor dim")
    ap.add_argument("--devices", type=int, default=1)
    args = ap.parse_args()

    n_clusters = 20
    cluster_size = max(8, int(args.n * 0.35) // n_clusters)
    spec = make_blobs_with_noise(
        n_clusters, cluster_size, args.n - n_clusters * cluster_size,
        d=args.d, seed=7)
    print(f"[pipeline] {args.n} descriptors, {n_clusters} visual-word "
          f"clusters of ~{cluster_size}, rest noise")

    if args.devices > 1:
        mesh = make_mesh((args.devices,), ("data",))
        ctx = MeshContext(mesh=mesh, data_axes=("data",), model_axis="data")
        espec = EngineSpec(engine="mesh", mesh_ctx=ctx)
        mode = f"PALID x{args.devices}"
    else:
        espec = EngineSpec(engine="replicated")
        mode = "ALID serial"
    cfg = ALIDConfig(a_cap=max(64, cluster_size + 32), delta=128,
                     lsh=auto_lsh_params(spec.points),
                     seeds_per_round=32, max_rounds=48, spec=espec)
    t0 = time.time()
    res = fit(spec.points, cfg, jax.random.PRNGKey(1))
    dt = time.time() - t0

    sizes = np.bincount(res.labels[res.labels >= 0]) if res.n_clusters else []
    print(f"[pipeline] {mode}: {dt:.1f}s, {res.n_clusters} clusters, "
          f"sizes {sorted(sizes.tolist(), reverse=True)[:10]}...")
    print(f"[pipeline] AVG-F = {avg_f1_score(spec.labels, res.labels):.3f}")


if __name__ == "__main__":
    main()
