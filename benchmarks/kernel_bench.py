"""Kernel micro-benchmarks + the per-op device-perf model. On this CPU
container the production dispatch is the jnp reference path (what XLA lowers
for the dry-run); Pallas interpret mode is a correctness vehicle, not a speed
one — wall numbers here are the CPU ref path, per call, after jit warmup.

The fused-op section times each fused kernel (ref path) against the
historical UNFUSED composition it replaced and writes the pairs to
BENCH_kernels.json (schema v2):

  - affinity_matvec / roi_filter / assign: the pre-fusion multi-sweep XLA
    composition vs the single fused op, both inside one jit.
  - lid_sweep: per-iteration op granularity (T calls of an n_steps=1 chunk,
    state threaded through the host — the pre-sweep `lid_solve` launch
    pattern, one kernel dispatch per LID iteration) vs ONE fused n_steps=T
    sweep call. The chunking bit-parity property guarantees both arms
    execute the identical iteration sequence.

Timing is interleaved and PAIRED: the two arms alternate call order across
reps, each rep measures both arms back-to-back (common-mode load cancels in
the per-rep ratio), and the comparison statistic is the median of per-rep
fused/unfused ratios. Sequential A-then-B timing on this container showed
phantom ~20% gaps between bit-identical programs; naive independent medians
still drift ~+/-6%. Any fused arm whose paired ratio exceeds the 10% noise
floor is reported in the JSON "warnings" list — CI treats that as a
regression signal. (The floor comes from A/A calibration: the SAME compiled
program timed as both arms yields paired ratios in ~[0.95, 1.05] on this
shared-VM container, occasionally to 1.10; a sub-floor delta carries no
information.)

Each op also carries an analytic device model (flops, HBM bytes, arithmetic
intensity) and, on a chip, its roofline placement against that chip's
published peaks (`benchmarks.roofline.DEVICE_PEAKS`, keyed by device_kind;
none on a CPU) — this is the per-op half of the device-perf report;
`benchmarks.run --device-report` merges it with the per-cell roofline
rows."""

from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_line
from benchmarks.roofline import peaks
from repro.kernels import ref


def timeit(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters * 1e6


def timeit_pair(fn_a, fn_b, *, iters=30, reps=15):
    """Interleaved paired timer for two (argless, pre-bound) arms: each rep
    measures both back-to-back (order alternating across reps) so slow load
    drift cancels in the per-rep ratio. Returns (median us/call of a,
    median us/call of b, median per-rep a/b ratio) — the RATIO is the
    comparison statistic; the medians are informational. Both arms are
    warmed before timing."""
    jax.block_until_ready(fn_a())
    jax.block_until_ready(fn_b())
    acc_a, acc_b, ratios = [], [], []
    for r in range(reps):
        pairs = [(fn_a, acc_a), (fn_b, acc_b)]
        if r % 2:
            pairs.reverse()
        for fn, acc in pairs:
            t0 = time.perf_counter()
            for _ in range(iters):
                jax.block_until_ready(fn())
            acc.append((time.perf_counter() - t0) / iters * 1e6)
        ratios.append(acc_a[-1] / acc_b[-1])
    return (float(np.median(acc_a)), float(np.median(acc_b)),
            float(np.median(ratios)))


@functools.lru_cache(maxsize=None)
def _device_peaks() -> dict | None:
    """Published peaks of the chip these timings run on (an unknown
    accelerator kind is an error); None on a CPU, which has no entry: the
    ref oracles timed there get no roofline placement."""
    dev = jax.devices()[0]
    return None if dev.platform == "cpu" else peaks(dev.device_kind)


def _roofline(flops: float, hbm_bytes: float) -> dict:
    """Single-chip placement for one op on the running device: analytic
    compute/memory times against the same published peaks roofline.py uses
    for the program-level table, plus the compute fraction of the binding
    term. Off a chip only the analytic counts are kept."""
    model = {"flops": flops, "hbm_bytes": hbm_bytes,
             "intensity_flops_per_byte": flops / hbm_bytes}
    pk = _device_peaks()
    if pk is None:
        return model
    t_comp = flops / pk["flops_bf16"]
    t_mem = hbm_bytes / pk["hbm_bytes_s"]
    bound = max(t_comp, t_mem)
    return {
        **model,
        "t_compute_s": t_comp,
        "t_memory_s": t_mem,
        "bound": "compute" if t_comp >= t_mem else "memory",
        "roofline_frac": t_comp / bound,
    }


def _bench_fused(rng) -> dict:
    """Fused vs unfused timings + analytic device model for the fused ops."""
    out = {}
    cap, a_cap, d = 192, 64, 64
    k = jnp.float32(0.4)

    # --- Ax refresh: masked affinity x weights matvec ----------------------
    v = jnp.asarray(rng.normal(size=(cap, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 4096, cap), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, cap).astype(bool))
    w = jnp.where(mask, jnp.asarray(rng.uniform(0, 1, cap), jnp.float32), 0.0)

    def unfused_mv(v, idx, mask, w):
        a = ref.affinity_ref(v, v, k)
        a = jnp.where(idx[:, None] == idx[None, :], 0.0, a)
        a = a * (mask[:, None] & mask[None, :])
        return a @ w

    def fused_mv(v, idx, mask, w):
        return jnp.where(mask, ref.affinity_matvec_ref(v, idx, v, idx, w, k),
                         0.0)

    jf, ju = jax.jit(fused_mv), jax.jit(unfused_mv)
    us_f, us_u, ratio = timeit_pair(lambda: jf(v, idx, mask, w),
                                    lambda: ju(v, idx, mask, w))
    csv_line("kernel/affinity_matvec_192_unfused", us_u, "cap=192,d=64")
    csv_line("kernel/affinity_matvec_192_fused", us_f,
             f"speedup={us_u / us_f:.2f}x")
    # fused: one (cap, d) load, the (cap, cap) affinity block lives in VMEM
    out["affinity_matvec"] = {
        "shape": [cap, d], "unfused_us": us_u, "fused_us": us_f,
        "speedup": us_u / us_f, "paired_ratio": ratio,
        "model": _roofline(flops=cap * cap * (3 * d + 5) + 2 * cap * cap,
                           hbm_bytes=4 * (cap * d + 3 * cap) + cap),
    }

    # --- CIVS ROI filter ---------------------------------------------------
    n_cand = a_cap * 4 * 16                       # a_cap * L * probe
    vc = jnp.asarray(rng.normal(size=(n_cand, d)), jnp.float32)
    cen = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    val = jnp.asarray(rng.integers(0, 2, n_cand).astype(bool))
    rad = jnp.float32(0.8 * np.sqrt(d))

    def unfused_roi(vc, cen, val):
        dist = jnp.sqrt(jnp.maximum(
            jnp.sum((vc - cen[None, :]) ** 2, -1), 0.0))  # analysis: allow(private-distance): unfused legacy composition, benchmarked as the comparison arm against the fused roi_filter kernel
        ok = val & (dist <= rad)
        return dist, ok, jnp.where(ok, -dist, -jnp.inf)

    def fused_roi(vc, cen, val):
        return ref.roi_filter_ref(vc, cen, rad, val)

    jf, ju = jax.jit(fused_roi), jax.jit(unfused_roi)
    us_f, us_u, ratio = timeit_pair(lambda: jf(vc, cen, val),
                                    lambda: ju(vc, cen, val),
                                    iters=100, reps=21)
    csv_line("kernel/roi_filter_4k_unfused", us_u, f"cands={n_cand},d=64")
    csv_line("kernel/roi_filter_4k_fused", us_f,
             f"speedup={us_u / us_f:.2f}x")
    out["roi_filter"] = {
        "shape": [n_cand, d], "unfused_us": us_u, "fused_us": us_f,
        "speedup": us_u / us_f, "paired_ratio": ratio,
        "model": _roofline(flops=n_cand * (3 * d + 3),
                           hbm_bytes=4 * (n_cand * d + d + 2 * n_cand)
                           + 2 * n_cand),
    }

    # --- batched assignment ------------------------------------------------
    n_clusters, m = 32, 4096
    sup_v = jnp.asarray(rng.normal(size=(n_clusters, a_cap, d)), jnp.float32)
    sup_w = jnp.asarray(rng.uniform(0, 1, (n_clusters, a_cap)), jnp.float32)
    dens = jnp.asarray(rng.uniform(0.5, 1.0, n_clusters), jnp.float32)
    q = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    thr = jnp.float32(0.5)

    def unfused_assign(q, sup_v, sup_w, dens):
        scores = jax.vmap(lambda v, wc: ref.affinity_ref(q, v, k) @ wc,
                          in_axes=(0, 0), out_axes=1)(sup_v, sup_w)
        best = jnp.argmax(scores, axis=1)
        ok = jnp.max(scores, axis=1) >= thr * dens[best]
        return jnp.where(ok, best, -1).astype(jnp.int32)

    def fused_assign(q, sup_v, sup_w, dens):
        return ref.assign_ref(q, sup_v, sup_w, dens, k, thr)[0]

    jf, ju = jax.jit(fused_assign), jax.jit(unfused_assign)
    us_f, us_u, ratio = timeit_pair(lambda: jf(q, sup_v, sup_w, dens),
                                    lambda: ju(q, sup_v, sup_w, dens),
                                    iters=3, reps=11)
    csv_line("kernel/assign_4kx32_unfused", us_u,
             f"q={m},C={n_clusters},A={a_cap}")
    csv_line("kernel/assign_4kx32_fused", us_f,
             f"speedup={us_u / us_f:.2f}x")
    n_sup = n_clusters * a_cap
    # epilogue is the per-cluster segment reduce (2 flops/support element)
    out["assign"] = {
        "shape": [m, n_clusters, a_cap, d], "unfused_us": us_u,
        "fused_us": us_f, "speedup": us_u / us_f, "paired_ratio": ratio,
        "model": _roofline(
            flops=m * n_sup * (3 * d + 2) + 2.0 * m * n_sup,
            hbm_bytes=4 * (m * d + n_sup * d + n_sup + m)),
    }

    # --- fused multi-iteration LID sweep -----------------------------------
    # One seed's (cap, d) support block, T infection-immunization iterations.
    # Unfused arm = the pre-sweep per-iteration launch pattern: T dispatches
    # of an n_steps=1 chunk with x/ax/n_iters/converged threaded through the
    # host. Fused arm = ONE n_steps=T sweep call. Identical executed
    # iterations (chunking bit-parity), so the delta is pure launch + HBM
    # re-load amortization — the tentpole's claim.
    import functools

    from repro.core import lid
    from repro.kernels import ops

    T = 8
    centers = rng.normal(size=(4, d)) * 3
    pts = np.concatenate([c + rng.normal(size=(cap // 4, d))
                          for c in centers])
    v_beta = jnp.asarray(pts, jnp.float32)
    bidx = jnp.arange(cap, dtype=jnp.int32)
    bmask = jnp.ones(cap, bool)
    st = lid.init_state(v_beta, jnp.int32(0), cap)._replace(
        beta_idx=bidx, beta_mask=bmask, v_beta=v_beta)
    st = lid.refresh_ax(st, k, backend="ref")   # live Ax so LID iterates

    sweep_T = jax.jit(functools.partial(
        ops.lid_sweep, n_steps=T, max_iters=T, tol=1e-5, backend="ref"))
    sweep_1 = jax.jit(functools.partial(
        ops.lid_sweep, n_steps=1, max_iters=T, tol=1e-5, backend="ref"))

    def fused_sweep():
        return sweep_T(st.v_beta, st.beta_idx, st.beta_mask, st.x, st.ax,
                       st.n_iters, st.converged, k)

    def unfused_sweep():
        x, ax, it, cv = st.x, st.ax, st.n_iters, st.converged
        for _ in range(T):
            x, ax, it, cv = sweep_1(st.v_beta, st.beta_idx, st.beta_mask,
                                    x, ax, it, cv, k)
        return x, ax, it, cv

    rf, ru = fused_sweep(), unfused_sweep()
    if not all(bool(jnp.all(a == b)) for a, b in zip(rf, ru)):
        raise AssertionError("lid_sweep chunking bit-parity broken")

    us_f, us_u, ratio = timeit_pair(fused_sweep, unfused_sweep)
    csv_line("kernel/lid_sweep_192x8_unfused", us_u,
             f"cap={cap},d={d},T={T},per-iter dispatch")
    csv_line("kernel/lid_sweep_192x8_fused", us_f,
             f"speedup={us_u / us_f:.2f}x")
    # per iteration: one on-demand column (3d+2 flops/row) + O(cap) updates;
    # fused HBM traffic: the block loads ONCE for all T iterations
    out["lid_sweep"] = {
        "shape": [cap, d, T], "unfused_us": us_u, "fused_us": us_f,
        "speedup": us_u / us_f, "paired_ratio": ratio,
        "model": _roofline(flops=T * cap * (3 * d + 12),
                           hbm_bytes=4 * (cap * d + 4 * cap) + cap),
    }
    return out


def main(quick: bool = True):
    rng = np.random.default_rng(0)

    fused = _bench_fused(rng)
    # 10% noise floor on the PAIRED ratio, from A/A calibration (module
    # docstring): identical programs reach ~1.05, occasionally 1.10, here
    warn_rel = 1.10
    warnings = [
        f"{name}: fused arm slower than unfused oracle "
        f"(paired fused/unfused ratio {rec['paired_ratio']:.3f} > "
        f"{warn_rel}; {rec['fused_us']:.1f}us vs {rec['unfused_us']:.1f}us)"
        for name, rec in fused.items()
        if rec["paired_ratio"] > warn_rel
    ]
    for wtext in warnings:
        csv_line("kernel/WARNING", 0, wtext)
    dev = jax.devices()[0]
    with open("BENCH_kernels.json", "w") as f:
        json.dump({"version": 2,
                   "backend": "ref",
                   "device": {"platform": dev.platform,
                              "kind": dev.device_kind,
                              "count": len(jax.devices())},
                   "warn_rel_noise_floor": warn_rel,
                   "roofline_model": (None if _device_peaks() is None else
                                      {"device_kind": dev.device_kind,
                                       **_device_peaks()}),
                   "fused_ops": fused,
                   "warnings": warnings}, f, indent=2)

    q = jnp.asarray(rng.normal(size=(1024, 64)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(4096, 64)), jnp.float32)
    aff = jax.jit(lambda a, b: ref.affinity_ref(a, b, jnp.float32(0.2)))
    us = timeit(aff, q, c)
    csv_line("kernel/affinity_1kx4k_d64", us,
             f"gflops={2*1024*4096*64/us/1e3:.1f}")

    qq = jnp.asarray(rng.normal(size=(1, 8, 512, 64)), jnp.bfloat16)
    kk = jnp.asarray(rng.normal(size=(1, 2, 512, 64)), jnp.bfloat16)
    att = jax.jit(lambda a, b, v: ref.attention_ref(a, b, v, causal=True))
    us = timeit(att, qq, kk, kk)
    csv_line("kernel/flash_attn_ref_512", us,
             f"gflops={4*8*512*512*64/us/1e3:.1f}")

    msg = jnp.asarray(rng.normal(size=(20000, 64)), jnp.float32)
    seg = jnp.asarray(np.sort(rng.integers(0, 2000, 20000)), jnp.int32)
    sm = jax.jit(lambda m, s: ref.segment_matmul_ref(m, s, 2000))
    us = timeit(sm, msg, seg)
    csv_line("kernel/segment_sum_20k_d64", us, f"edges_per_us={20000/us:.1f}")

    table = jnp.asarray(rng.normal(size=(100000, 32)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 100000, 8192), jnp.int32)
    bags = jnp.asarray(np.sort(rng.integers(0, 1024, 8192)), jnp.int32)
    eb = jax.jit(lambda t, i, b: ref.embedding_bag_ref(t, i, b, 1024))
    us = timeit(eb, table, idx, bags)
    csv_line("kernel/embedding_bag_8k", us, f"lookups_per_us={8192/us:.1f}")

    x = jnp.asarray(rng.normal(size=(8192, 64)), jnp.float32)
    proj = jnp.asarray(rng.normal(size=(4, 8, 64)), jnp.float32)
    bias = jnp.asarray(rng.uniform(0, 1, size=(4, 8)), jnp.float32)
    lh = jax.jit(lambda a, p, b: ref.lsh_hash_ref(a, p, b, 1.0))
    us = timeit(lh, x, proj, bias)
    csv_line("kernel/lsh_hash_8k_L4m8", us, f"points_per_us={8192/us:.1f}")


if __name__ == "__main__":
    main(quick=False)
