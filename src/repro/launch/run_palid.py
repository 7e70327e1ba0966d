"""PALID launcher — the paper's headline workload (Sec. 5.3): dominant-cluster
detection over SIFT-like descriptor collections, parallelized over a mesh.
Drives the unified engine facade (`repro.core.engine.fit`); --engine (or the
legacy --devices/--shards pair) selects the EngineSpec, --source feeds a real
dataset through the DataSource ingestion API instead of the synthetic blobs.

  # 8 virtual devices (the Spark-executor analogue of Table 2):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
      python -m repro.launch.run_palid --n 20000 --d 32 --devices 8

  # out-of-core: dataset + LSH split into 16 shards, 2 per device's HBM
  # (the >HBM path, DESIGN.md §3):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
      python -m repro.launch.run_palid --n 20000 --d 32 --devices 8 --shards 16

  # host-streamed over an on-disk npy that never materializes in RAM/HBM
  # (DESIGN.md §3.3 — peak device memory O(shard + cap)):
  PYTHONPATH=src python -m repro.launch.run_palid \\
      --source memmap:descriptors.npy --engine streamed --shards 16
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core.alid import ALIDConfig, EngineSpec
from repro.core.engine import fit, make_engine
from repro.core.source import make_source, strided_sample_indices
from repro.data import auto_lsh_params, make_blobs_with_noise
from repro.data.synthetic import sample_nn_distances
from repro.distributed.context import MeshContext, make_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.utils import avg_f1_score


def engine_spec(engine: str, devices: int, shards: int, chunk_size: int,
                cache_bytes: int = EngineSpec._field_defaults["cache_bytes"],
                prefetch_depth: int = (
                    EngineSpec._field_defaults["prefetch_depth"]),
                scratch_dir: str = "",
                backend: str = "auto",
                dtype: str = "float32") -> EngineSpec:
    """Resolve --engine (+ legacy --devices/--shards) into an EngineSpec.

    The pipeline knobs only matter for engine="streamed": `cache_bytes`
    bounds the host LRU of shard bundles, `prefetch_depth` sizes the
    background reader's slot ring (0 = synchronous double-buffer), and
    `scratch_dir` places the build-time scratch memmap ("" = system temp
    dir, "none" disables persistence). `backend` is the kernel backend for
    every hot-path op (repro.kernels.ops); `dtype` the point storage dtype
    (mixed precision: bf16 storage, f32 accumulators)."""
    scratch: str | None = None if scratch_dir == "none" else scratch_dir
    if engine == "auto":
        if devices > 1:
            engine = "mesh"
        elif shards > 0:
            engine = "sharded"
        else:
            engine = "replicated"
    if engine == "mesh":
        mesh = make_mesh((max(devices, 1),), ("data",))
        ctx = MeshContext(mesh=mesh, data_axes=("data",), model_axis="data")
        return EngineSpec(engine="mesh", n_shards=shards, mesh_ctx=ctx,
                          chunk_size=chunk_size, backend=backend,
                          dtype=dtype)
    if engine == "streamed":
        # 0 lets StreamedEngine apply its own default (8) — forcing 1 here
        # would stream the whole dataset as a single O(n·d) bundle
        return EngineSpec(engine="streamed", n_shards=shards,
                          chunk_size=chunk_size, cache_bytes=cache_bytes,
                          prefetch_depth=prefetch_depth, scratch_dir=scratch,
                          backend=backend, dtype=dtype)
    if engine == "sharded":
        return EngineSpec(engine="sharded", n_shards=max(1, shards),
                          chunk_size=chunk_size, backend=backend,
                          dtype=dtype)
    return EngineSpec(engine="replicated", chunk_size=chunk_size,
                      backend=backend, dtype=dtype)


def synthetic_deployment(n: int, d: int, clusters: int, seed: int = 0):
    """The synthetic workload this launcher fits: `clusters` planted
    Gaussian blobs holding 40% of the n points, the rest uniform noise
    (`make_blobs_with_noise`), with LSH parameters calibrated on it and the
    support capacity sized to one planted cluster plus slack.
    Returns (SyntheticSpec, LSHParams, a_cap).

    The LSH segment length is 8x the 10th percentile of sampled
    nearest-neighbour distances, not `auto_lsh_params`' median: the
    percentile has to fall among the planted points' distances, and with
    this generator's 60% uniform noise at d = 128 the median is the noise
    scale (~12x the intra-cluster distance), which puts a third of the data
    in every bucket so CIVS retrieves near-random candidates."""
    cluster_size = max(4, int(n * 0.4) // clusters)
    spec = make_blobs_with_noise(clusters, cluster_size,
                                 n - clusters * cluster_size, d=d, seed=seed)
    seg_len = 8.0 * float(np.percentile(sample_nn_distances(spec.points), 10))
    return (spec, auto_lsh_params(spec.points)._replace(seg_len=seg_len),
            max(64, cluster_size + 32))


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--d", type=int, default=32)
    ap.add_argument("--clusters", type=int, default=20)
    ap.add_argument("--devices", type=int, default=0,  # 0 = serial ALID
                    help="data-axis size for the mesh engine (0 = serial)")
    ap.add_argument("--shards", type=int, default=0,
                    help="ShardedStore/StreamedStore shard count for "
                         "out-of-core CIVS (0 = replicated dataset + LSH; "
                         "must divide evenly over --devices when both are "
                         "set)")
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "replicated", "sharded", "mesh",
                             "streamed"],
                    help="EngineSpec.engine; 'auto' keeps the legacy "
                         "--devices/--shards mapping")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "ref", "pallas", "interpret"],
                    help="kernel backend for every hot-path op "
                         "(EngineSpec.backend -> repro.kernels.ops): 'auto' "
                         "= env/platform dispatch, 'ref' = pure-jnp "
                         "oracles, 'pallas' = compiled TPU kernels, "
                         "'interpret' = Pallas kernels emulated as jax ops "
                         "(CI parity smoke)")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="point STORAGE dtype (EngineSpec.dtype): bfloat16 "
                         "halves store/HBM bytes while every distance, "
                         "affinity and LID accumulator stays f32 (mixed "
                         "precision; support sets typically match f32)")
    ap.add_argument("--quick", action="store_true",
                    help="small-n smoke preset (n=600 d=8, few rounds) — "
                         "used by CI for the --backend interpret smoke")
    ap.add_argument("--source", default="",
                    help="ingest a real dataset instead of synthetic blobs: "
                         "'memmap:path.npy' (out-of-core) or 'npy:path.npy' "
                         "(in host RAM); --n/--d/--clusters are ignored")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="host chunk rows for source-chunked builds "
                         "(0 = default)")
    ap.add_argument("--cache-bytes", type=int,
                    default=EngineSpec._field_defaults["cache_bytes"],
                    help="streamed engine: host LRU budget for shard "
                         "bundles in bytes (<=0 disables the cache)")
    ap.add_argument("--prefetch-depth", type=int,
                    default=EngineSpec._field_defaults["prefetch_depth"],
                    help="streamed engine: slot-ring depth of the "
                         "background shard reader (0 = synchronous "
                         "double-buffer, no reader thread)")
    ap.add_argument("--scratch-dir", default="",
                    help="streamed engine: directory for the build-time "
                         "scratch memmap of reordered shard payloads "
                         "('' = system temp dir, 'none' = disable "
                         "persistence)")
    ap.add_argument("--profile", action="store_true",
                    help="print the pipeline stage report (read/put/"
                         "compute/wait seconds, cache + prefetch hit "
                         "rates) after the fit")
    ap.add_argument("--serve-bench", action="store_true",
                    help="after the fit, stand up the continuous-batching "
                         "assignment server over the result and drive it "
                         "with open-loop traffic; prints p50/p99 latency, "
                         "throughput and batch occupancy")
    ap.add_argument("--serve-rate", type=float, default=2000.0,
                    help="--serve-bench open-loop arrival rate (req/s)")
    ap.add_argument("--online", action="store_true",
                    help="after the fit, drive the online-update round trip:"
                         " wrap the result in an OnlineClustering, publish "
                         "it to a live tenant, insert a delta, commit + "
                         "hot-swap, roll back to the pre-insert epoch and "
                         "ASSERT the restored labels are bit-identical "
                         "while submit() traffic keeps serving")
    ap.add_argument("--inject-faults", default="", metavar="SPEC",
                    help="chaos demo: re-run the fit under injected faults "
                         "and assert label parity with the clean run. SPEC "
                         "is comma-separated name:value pairs — "
                         "'transient:0.1' (seeded transient read-error "
                         "rate), 'corrupt:0.05' (scratch-slab corruption "
                         "rate per fetch; streamed engine with scratch "
                         "only), 'kill-reader:3' (kill the prefetch reader "
                         "at the k-th bundle; streamed + prefetch only). "
                         "Prints a greppable 'fault-parity=True' line")
    ap.add_argument("--checkpoint-dir", default="",
                    help="persist round-level fit state here (resume point "
                         "every --checkpoint-every rounds); with "
                         "--inject-faults, also runs a crash-at-round-2 + "
                         "resume arm and prints 'resume-parity=True'")
    ap.add_argument("--checkpoint-every", type=int, default=1,
                    help="rounds between fit checkpoints (default 1)")
    ap.add_argument("--resume", action="store_true",
                    help="resume the fit from the latest intact checkpoint "
                         "in --checkpoint-dir (bit-identical to the "
                         "uninterrupted run)")
    ap.add_argument("--a-cap", type=int, default=0,
                    help="support capacity override (0 = auto)")
    ap.add_argument("--seeds-per-round", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=64)
    ap.add_argument("--check", action="store_true",
                    help="run the static/runtime contract checker instead "
                         "of a fit — alias for `python -m "
                         "repro.analysis.check --report CHECK_report.json` "
                         "(exits non-zero on any unsuppressed violation)")
    args = ap.parse_args()
    if args.check:
        from repro.analysis import check as _check
        raise SystemExit(_check.main(["--report", "CHECK_report.json"]))
    if args.quick:
        args.n, args.d, args.clusters = 600, 8, 4
        args.rounds = min(args.rounds, 8)
        args.seeds_per_round = min(args.seeds_per_round, 8)

    spec = None
    if args.source:
        source = make_source(args.source)
        # calibrate LSH scale on a strided subsample — never the full file
        calib = source.sample(strided_sample_indices(source.n, 512))
        lshp = auto_lsh_params(calib)
        a_cap = args.a_cap or 128
        n, d = source.n, source.dim
    else:
        spec, lshp, a_cap = synthetic_deployment(args.n, args.d,
                                                 args.clusters)
        source = spec.points
        a_cap = args.a_cap or a_cap
        n, d = spec.points.shape

    cfg = ALIDConfig(a_cap=a_cap, delta=128, lsh=lshp,
                     seeds_per_round=args.seeds_per_round,
                     max_rounds=args.rounds,
                     spec=engine_spec(args.engine, args.devices, args.shards,
                                      args.chunk_size, args.cache_bytes,
                                      args.prefetch_depth, args.scratch_dir,
                                      args.backend, args.dtype))
    # build the engine here (instead of letting fit do it) so --profile can
    # read its stage counters after the run; we own close() in exchange
    engine = make_engine(cfg.spec)
    try:
        t0 = time.time()
        res = fit(source, cfg, jax.random.PRNGKey(0), engine=engine,
                  checkpoint_dir=args.checkpoint_dir or None,
                  checkpoint_every=args.checkpoint_every,
                  resume=args.resume)
        dt = time.time() - t0
        n_members = int((res.labels >= 0).sum())
        line = (f"[palid] n={n} d={d} engine={cfg.spec.engine} "
                f"backend={cfg.spec.backend} dtype={cfg.spec.dtype} "
                f"devices={max(args.devices, 1)} shards={args.shards} "
                f"time={dt:.2f}s clusters={res.n_clusters} "
                f"members={n_members}")
        if spec is not None:
            line += f" AVG-F={avg_f1_score(spec.labels, res.labels):.3f}"
        print(line)
        if args.profile:
            stats = getattr(engine, "stats", None)
            print(f"[palid] {stats.report()}" if stats is not None else
                  f"[palid] --profile: engine {cfg.spec.engine!r} has no "
                  "pipeline stats (streamed only)")
        if args.serve_bench:
            _serve_bench(res, source, args.serve_rate)
        if args.online:
            _online_demo(res, source, cfg)
        if args.inject_faults:
            _chaos_demo(res, source, cfg, args)
    finally:
        engine.close()


def _parse_faults(spec: str) -> dict:
    out = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, value = part.partition(":")
        if name not in ("transient", "corrupt", "kill-reader"):
            raise SystemExit(
                f"--inject-faults: unknown fault {name!r} (expected "
                "transient|corrupt|kill-reader)")
        out[name] = float(value) if value else 0.0
    return out


def _chaos_demo(clean, source, cfg, args) -> None:
    """Re-run the just-finished fit under injected faults and assert the
    labels are BIT-IDENTICAL to the clean result (the DESIGN.md §11
    contract); with --checkpoint-dir, also crash at round 2 and resume.
    Prints one greppable line — the CI chaos step asserts on it."""
    import os


    from repro.core.resilience import (FaultySource, PipelineFaults,
                                       RetryPolicy)
    from repro.core.source import as_source

    faults = _parse_faults(args.inject_faults)
    fast = RetryPolicy(base_delay=0.001, max_delay=0.05)
    faulty = FaultySource(as_source(source),
                          rate=faults.get("transient", 0.0), seed=1)
    engine = make_engine(cfg.spec)
    pf = None
    if faults.get("corrupt", 0.0) > 0.0 or "kill-reader" in faults:
        pf = PipelineFaults(corrupt_rate=faults.get("corrupt", 0.0),
                            kill_reader_at=int(faults.get("kill-reader",
                                                          -1.0)),
                            seed=2)
        engine.faults = pf
    try:
        res = fit(faulty, cfg, jax.random.PRNGKey(0), engine=engine,
                  retry_policy=fast)
        stats = getattr(engine, "stats", None)
        corruptions = int(stats.corruptions) if stats is not None else 0
        deaths = int(stats.reader_deaths) if stats is not None else 0
    finally:
        engine.close()
    parity = bool(np.array_equal(clean.labels, res.labels)
                  and res.n_rounds == clean.n_rounds)

    resume_txt = ""
    if args.checkpoint_dir:
        ckpt = os.path.join(args.checkpoint_dir, "chaos")
        try:
            fit(source, cfg, jax.random.PRNGKey(0), checkpoint_dir=ckpt,
                checkpoint_every=args.checkpoint_every, crash_at_round=2)
        except RuntimeError:
            pass                      # the injected crash
        resumed = fit(source, cfg, jax.random.PRNGKey(0),
                      checkpoint_dir=ckpt, resume=True)
        resume_ok = bool(np.array_equal(clean.labels, resumed.labels)
                         and resumed.n_rounds == clean.n_rounds)
        resume_txt = f" resume-parity={resume_ok}"

    print(f"[palid] chaos faults={args.inject_faults!r} "
          f"injected={faulty.injected} corruptions={corruptions} "
          f"reader_deaths={deaths} retries_ok=True "
          f"fault-parity={parity}{resume_txt}")


def _serve_bench(res, source, rate_hz: float) -> None:
    """Open-loop traffic against the continuous-batching assignment server,
    replaying rows of the just-fitted dataset as queries."""

    from repro.core.source import as_source
    from repro.serve import ClusterServer, run_open_loop

    if res.n_clusters == 0:
        print("[palid] --serve-bench: fit produced 0 clusters, skipping")
        return
    src = as_source(source)
    n_q = min(src.n, 1024)
    rng = np.random.default_rng(0)
    queries = src.sample(np.sort(rng.choice(src.n, size=n_q, replace=False)))
    with ClusterServer(batch_slots=64, queue_limit=max(128, n_q),
                       policy="block") as server:
        server.add_tenant("default", res)
        server.submit(queries[0]).result(timeout=30)   # warm the jit
        out = run_open_loop(server, queries, rate_hz)
        occ = server.stats.occupancy(64)
    print(f"[palid] serve n={n_q} rate={rate_hz:.0f}rps "
          f"p50={out['latency_ms_p50']:.2f}ms "
          f"p99={out['latency_ms_p99']:.2f}ms "
          f"tput={out['throughput_rps']:.0f}rps occupancy={occ:.2f}")


def _online_demo(res, source, cfg) -> None:
    """Insert → commit → rollback → re-serve round trip over the live
    serving stack (what the CI online smoke drives): the rollback must
    restore the pre-insert label array BIT-IDENTICALLY from the
    checkpoint/manager.py snapshot, with the tenant hot-swapping versions
    while submits keep flowing."""

    from repro.core.online import OnlineClustering
    from repro.core.source import as_source
    from repro.serve import ClusterServer, LiveServing

    src = as_source(source)
    pts = np.asarray(src.sample(np.arange(src.n)), np.float32)
    oc = OnlineClustering(res, pts, cfg)
    pre_labels = oc.labels.copy()
    base_epoch = oc.epoch_id
    rng = np.random.default_rng(0)
    with ClusterServer(batch_slots=32, queue_limit=256,
                       policy="block") as server:
        live = LiveServing(server, oc, name="palid")
        live.publish()
        probe = pts[0]
        lab_pre = live.submit(probe).result(timeout=30)
        # delta: jittered copies of labeled points — guaranteed to land
        # inside existing outer ROI balls and exercise the warm-start path
        labeled = np.flatnonzero(pre_labels >= 0)
        take = (labeled[rng.choice(labeled.size, size=min(8, labeled.size),
                                   replace=False)]
                if labeled.size else np.arange(min(8, len(pts))))
        delta = pts[take] + 0.01 * rng.standard_normal(
            (take.size, pts.shape[1])).astype(np.float32)
        ids = oc.insert(delta)
        ep, _ = live.commit_and_publish({"delta": int(ids.size)})
        eid, _ = live.rollback_and_publish(base_epoch)
        lab_post = live.submit(probe).result(timeout=30)
        assert np.array_equal(oc.labels, pre_labels), (
            "post-rollback labels differ from the pre-insert snapshot")
        assert lab_post == lab_pre, (lab_post, lab_pre)
        info = server.tenant_info()["palid"]
        s = server.stats.snapshot()
    o = oc.stats.snapshot()
    print(f"[palid] online insert={ids.size} routed={o['routed']} "
          f"buffered={o['buffered']} commit=epoch{ep.id} "
          f"rollback=epoch{eid} bit-identical=True "
          f"versions={[r['version'] for r in info]} "
          f"active_epoch={[r['epoch'] for r in info if r['active']][0]} "
          f"swaps={s['version_swaps']} rollbacks={s['rollbacks']}")


if __name__ == "__main__":
    main()
