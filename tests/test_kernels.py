"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle, swept
over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.affinity import affinity_pallas
from repro.kernels.affinity_matvec import affinity_matvec_pallas
from repro.kernels.assign import assign_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lsh_hash import lsh_hash_pallas
from repro.kernels.roi_filter import roi_filter_pallas
from repro.kernels.segment_matmul import segment_matmul_pallas


# ------------------------------------------------------------- affinity ----
@pytest.mark.parametrize("m,n,d", [(16, 16, 8), (100, 50, 32), (130, 257, 100),
                                   (128, 128, 128), (1, 300, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_affinity_kernel(m, n, d, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(m, d)), dtype)
    c = jnp.asarray(rng.normal(size=(n, d)), dtype)
    k = jnp.float32(0.37)
    got = affinity_pallas(q, c, k, bm=64, bn=64, interpret=True)
    want = ref.affinity_ref(q, c, k)
    rtol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=rtol, atol=1e-4)


# ------------------------------------------------- fused affinity matvec ----
@pytest.mark.parametrize("m,n,d", [(16, 16, 8), (96, 33, 16), (130, 257, 100),
                                   (192, 64, 128), (1, 7, 5)])
def test_affinity_matvec_kernel(m, n, d):
    """Masked affinity x weights matvec vs the jnp oracle — shape sweep incl.
    ragged/padded tails (m and n off the 128 tile grid)."""
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    # overlapping index spaces -> some (i, j) pairs hit the diagonal zeroing
    q_idx = jnp.asarray(rng.integers(-1, max(m, n), m), jnp.int32)
    c_idx = jnp.asarray(rng.integers(-1, max(m, n), n), jnp.int32)
    w = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
    k = jnp.float32(0.37)
    got = affinity_matvec_pallas(q, q_idx, c, c_idx, w, k, bm=64,
                                 interpret=True)
    want = ref.affinity_matvec_ref(q, q_idx, c, c_idx, w, k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_affinity_matvec_matches_unfused_refresh():
    """The fused op must equal the unfused composition (affinity block ->
    diag zero -> mask -> order-pinned matvec) with masks folded into w/rows.
    The contraction in both arms is `ref.tree_matvec` — the op's defined
    reduction order — so this asserts the mask folding is exact, bitwise."""
    rng = np.random.default_rng(11)
    cap, d = 48, 12
    v = jnp.asarray(rng.normal(size=(cap, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 100, cap), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, cap).astype(bool))
    x = jnp.asarray(rng.uniform(0, 1, cap), jnp.float32)
    k = jnp.float32(0.8)
    w = jnp.where(mask, x, 0.0)

    a = ref.affinity_ref(v, v, k)
    a = jnp.where(idx[:, None] == idx[None, :], 0.0, a)
    a = a * (mask[:, None] & mask[None, :])
    want = ref.tree_matvec(a, w)

    got = ref.affinity_matvec_ref(v, idx, v, idx, w, k)
    got = jnp.where(mask, got, 0.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------- fused ROI filter ----
@pytest.mark.parametrize("n,d", [(64, 8), (777, 16), (4096, 32), (3, 100)])
def test_roi_filter_kernel(n, d):
    rng = np.random.default_rng(12)
    vc = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    center = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    valid = jnp.asarray(rng.integers(0, 2, n).astype(bool))
    radius = jnp.float32(0.9 * np.sqrt(d))    # keeps both branches populated
    gd, gv, gn = roi_filter_pallas(vc, center, radius, valid, bc=256,
                                   interpret=True)
    wd, wv, wn = ref.roi_filter_ref(vc, center, radius, valid)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    # -inf sentinel rows must agree exactly; finite scores to float tolerance
    np.testing.assert_array_equal(np.isinf(np.asarray(gn)),
                                  np.isinf(np.asarray(wn)))
    np.testing.assert_allclose(np.asarray(gn)[np.asarray(wv)],
                               np.asarray(wn)[np.asarray(wv)],
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ fused assign ----
@pytest.mark.parametrize("m,n_clusters,a,d", [(16, 3, 8, 8), (100, 5, 24, 16),
                                              (257, 2, 33, 100), (1, 1, 4, 6)])
def test_assign_kernel(m, n_clusters, a, d):
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    sup_v = jnp.asarray(rng.normal(size=(n_clusters, a, d)), jnp.float32)
    sup_w = jnp.asarray(rng.uniform(0, 1, (n_clusters, a)), jnp.float32)
    sup_w = sup_w / sup_w.sum(axis=1, keepdims=True)
    dens = jnp.asarray(rng.uniform(0.4, 1.0, n_clusters), jnp.float32)
    k = jnp.float32(0.5)
    thr = jnp.float32(0.5)
    gl, gs = assign_pallas(q, sup_v, sup_w, dens, k, thr, bm=64,
                           interpret=True)
    wl, ws = ref.assign_ref(q, sup_v, sup_w, dens, k, thr)
    np.testing.assert_array_equal(np.asarray(gl), np.asarray(wl))
    np.testing.assert_allclose(np.asarray(gs), np.asarray(ws),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_assign_clusters_valid_mask(backend):
    """Serving pad slots: a zero query row sitting right on top of a cluster
    near the origin MUST come back -1 (score 0) when its slot is masked
    invalid — on every backend, bit-identically to the unmasked labels for
    the valid slots."""
    from repro.kernels import ops
    rng = np.random.default_rng(15)
    n_clusters, a, d, m = 3, 8, 6, 10
    sup_v = jnp.asarray(rng.normal(scale=0.05, size=(n_clusters, a, d)),
                        jnp.float32)          # clusters hug the origin
    sup_w = jnp.full((n_clusters, a), 1.0 / a, jnp.float32)
    dens = jnp.asarray(rng.uniform(0.4, 0.9, n_clusters), jnp.float32)
    k, thr = jnp.float32(0.5), jnp.float32(0.5)
    q = jnp.asarray(rng.normal(scale=0.05, size=(m, d)), jnp.float32)
    q = q.at[m // 2:].set(0.0)                # "pad" rows: exact zeros
    valid = jnp.arange(m) < m // 2

    ul, us = ops.assign_clusters(q, sup_v, sup_w, dens, k, thr,
                                 backend=backend)
    ml, ms = ops.assign_clusters(q, sup_v, sup_w, dens, k, thr, valid,
                                 backend=backend)
    # unmasked, the zero rows DO match an origin cluster — that's the trap
    assert (np.asarray(ul[m // 2:]) >= 0).any()
    np.testing.assert_array_equal(np.asarray(ml[:m // 2]),
                                  np.asarray(ul[:m // 2]))
    np.testing.assert_allclose(np.asarray(ms[:m // 2]),
                               np.asarray(us[:m // 2]), rtol=1e-6)
    assert (np.asarray(ml[m // 2:]) == -1).all()
    assert (np.asarray(ms[m // 2:]) == 0.0).all()


def test_assign_ref_matches_legacy_predict_scores():
    """The fused assignment must reproduce the historical per-cluster
    vmapped score + argmax + threshold chain."""
    rng = np.random.default_rng(14)
    n_clusters, a, d, m = 4, 12, 10, 50
    q = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    sup_v = jnp.asarray(rng.normal(size=(n_clusters, a, d)), jnp.float32)
    sup_w = jnp.asarray(rng.uniform(0, 1, (n_clusters, a)), jnp.float32)
    dens = np.asarray(rng.uniform(0.2, 0.6, n_clusters), np.float32)
    k, thr = jnp.float32(0.45), 0.5

    def one(v, w):
        return ref.affinity_ref(q, v, k) @ w
    scores = np.asarray(jax.vmap(one, in_axes=(0, 0), out_axes=1)(
        sup_v, sup_w))
    best = scores.argmax(axis=1)
    ok = scores[np.arange(m), best] >= thr * dens[best]
    want = np.where(ok, best, -1).astype(np.int32)

    got, _ = ref.assign_ref(q, sup_v, sup_w, jnp.asarray(dens), k,
                            jnp.float32(thr))
    np.testing.assert_array_equal(np.asarray(got), want)


# ------------------------------------------------------- flash attention ----
@pytest.mark.parametrize("cfg", [
    dict(b=1, h=4, hkv=4, sq=128, sk=128, dh=32),                       # MHA
    dict(b=2, h=4, hkv=2, sq=64, sk=64, dh=16),                         # GQA
    dict(b=1, h=8, hkv=1, sq=100, sk=100, dh=32),                       # MQA+pad
    dict(b=1, h=2, hkv=2, sq=1, sk=256, dh=64, q_offset=255),           # decode
    dict(b=1, h=4, hkv=2, sq=128, sk=128, dh=32, window=32),            # SWA
    dict(b=1, h=4, hkv=2, sq=128, sk=128, dh=32, chunk=64),             # chunked
    dict(b=1, h=4, hkv=2, sq=128, sk=128, dh=32, softcap=20.0),         # softcap
    dict(b=1, h=4, hkv=4, sq=96, sk=192, dh=32, q_offset=96),           # chunked prefill
])
@pytest.mark.parametrize("dtype", [
    jnp.float32,
    pytest.param(jnp.bfloat16, marks=pytest.mark.slow),  # interpret-mode bf16 sweep is multi-minute on CPU
])
def test_flash_attention_kernel(cfg, dtype):
    rng = np.random.default_rng(1)
    b, h, hkv, sq, sk, dh = (cfg["b"], cfg["h"], cfg["hkv"], cfg["sq"],
                             cfg["sk"], cfg["dh"])
    q = jnp.asarray(rng.normal(size=(b, h, sq, dh)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, sk, dh)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, sk, dh)), dtype)
    kw = dict(causal=True, window=cfg.get("window"), chunk=cfg.get("chunk"),
              softcap=cfg.get("softcap"), q_offset=cfg.get("q_offset", 0))
    got = flash_attention_pallas(q, k, v, kw.pop("q_offset"), bq=32, bk=32,
                                 interpret=True, **kw)
    want = ref.attention_ref(q, k, v, q_offset=cfg.get("q_offset", 0),
                             causal=True, window=cfg.get("window"),
                             chunk=cfg.get("chunk"), softcap=cfg.get("softcap"))
    rtol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=rtol, atol=2e-3)


@pytest.mark.parametrize("cfg", [
    dict(b=3, h=2, hkv=2, sq=64, sk=64, dh=16),                   # causal
    dict(b=3, h=4, hkv=2, sq=64, sk=64, dh=16, window=16),        # SWA
    dict(b=2, h=2, hkv=2, sq=64, sk=64, dh=16, chunk=32),         # chunked
    dict(b=2, h=2, hkv=1, sq=1, sk=128, dh=16, q_offset=127),     # decode
])
def test_flash_attention_kv_start_parity(cfg):
    """Left-padded batches: per-row kv_start masks pad keys out and shifts
    positions to logical (slot - start), so window/chunk masks behave as if
    each row started at 0. Pallas(interpret) must match the ref oracle on
    every VALID query slot (fully-padded query rows are never consumed and
    the two backends legitimately differ there: ref emits uniform-softmax
    garbage, Pallas zeros)."""
    rng = np.random.default_rng(21)
    b, h, hkv, sq, sk, dh = (cfg["b"], cfg["h"], cfg["hkv"], cfg["sq"],
                             cfg["sk"], cfg["dh"])
    q = jnp.asarray(rng.normal(size=(b, h, sq, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, sk, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, sk, dh)), jnp.float32)
    kv_start = jnp.asarray(rng.integers(0, sk // 2, size=b), jnp.int32)
    q_offset = cfg.get("q_offset", 0)
    kw = dict(causal=True, window=cfg.get("window"), chunk=cfg.get("chunk"))
    got = flash_attention_pallas(q, k, v, q_offset, bq=32, bk=32,
                                 kv_start=kv_start, interpret=True, **kw)
    want = ref.attention_ref(q, k, v, q_offset=q_offset, kv_start=kv_start,
                             **kw)
    for i in range(b):
        first_valid = max(0, int(kv_start[i]) - q_offset)  # logical q slots
        np.testing.assert_allclose(
            np.asarray(got[i, :, first_valid:], np.float32),
            np.asarray(want[i, :, first_valid:], np.float32),
            rtol=2e-5, atol=2e-3)


def test_flash_attention_kv_start_matches_unpadded():
    """A row with kv_start=s must attend exactly as the same sequence run
    solo without padding — including under a sliding window, whose mask is
    NOT shift-invariant (the historical bug: window offsets computed in
    physical slots silently widened/narrowed per row)."""
    rng = np.random.default_rng(22)
    h, dh, s_real, pad = 2, 16, 48, 16
    sk = s_real + pad
    q_real = jnp.asarray(rng.normal(size=(1, h, s_real, dh)), jnp.float32)
    k_real = jnp.asarray(rng.normal(size=(1, h, s_real, dh)), jnp.float32)
    v_real = jnp.asarray(rng.normal(size=(1, h, s_real, dh)), jnp.float32)
    zq = jnp.zeros((1, h, pad, dh), jnp.float32)
    q_pad = jnp.concatenate([zq, q_real], axis=2)
    k_pad = jnp.concatenate([zq, k_real], axis=2)
    v_pad = jnp.concatenate([zq, v_real], axis=2)
    for window in (None, 16):
        solo = ref.attention_ref(q_real, k_real, v_real, causal=True,
                                 window=window)
        packed = ref.attention_ref(q_pad, k_pad, v_pad, causal=True,
                                   window=window,
                                   kv_start=jnp.asarray([pad], jnp.int32))
        np.testing.assert_allclose(np.asarray(packed[:, :, pad:]),
                                   np.asarray(solo), rtol=2e-5, atol=2e-5)


# --------------------------------------------------------- segment matmul ---
@pytest.mark.parametrize("e,n_seg,d", [(64, 16, 8), (300, 40, 32), (1000, 257, 16),
                                       (128, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_segment_matmul_kernel(e, n_seg, d, dtype):
    rng = np.random.default_rng(2)
    seg = np.sort(rng.integers(0, n_seg, size=e)).astype(np.int32)
    # add some padding at the end
    seg[-e // 10:] = -1
    seg = np.concatenate([np.sort(seg[seg >= 0]), seg[seg == -1]])
    msg = jnp.asarray(rng.normal(size=(e, d)), dtype)
    got = segment_matmul_pallas(msg, jnp.asarray(seg), n_seg, be=64, bw=32,
                                interpret=True)
    want = ref.segment_matmul_ref(msg, jnp.asarray(seg), n_seg)
    # rows in never-visited row blocks may be garbage in the raw kernel; the
    # ops wrapper masks them. Compare only visited row blocks here.
    visited = np.zeros(n_seg, bool)
    for s in seg[seg >= 0]:
        lo = (s // 32) * 32
        visited[lo:lo + 32] = True
    np.testing.assert_allclose(np.asarray(got)[visited],
                               np.asarray(want)[visited], rtol=1e-5, atol=1e-4)


def test_segment_matmul_ops_wrapper_masks_unvisited():
    import os
    os.environ["REPRO_KERNEL_INTERPRET"] = "1"
    try:
        from repro.kernels import ops
        rng = np.random.default_rng(3)
        seg = jnp.asarray(np.array([0, 0, 1, 5, 5, -1], np.int32))
        msg = jnp.asarray(rng.normal(size=(6, 8)), jnp.float32)
        got = ops.segment_matmul(msg, seg, 300, be=8, bw=8)
        want = ref.segment_matmul_ref(msg, seg, 300)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    finally:
        del os.environ["REPRO_KERNEL_INTERPRET"]


# ---------------------------------------------------------- embedding bag ---
@pytest.mark.parametrize("v,dim,n_idx,n_bags", [(100, 16, 64, 10),
                                                (1000, 32, 300, 50),
                                                (64, 128, 128, 128)])
def test_embedding_bag_kernel(v, dim, n_idx, n_bags):
    rng = np.random.default_rng(4)
    table = jnp.asarray(rng.normal(size=(v, dim)), jnp.float32)
    bags = np.sort(rng.integers(0, n_bags, size=n_idx)).astype(np.int32)
    idx = rng.integers(0, v, size=n_idx).astype(np.int32)
    idx[-n_idx // 8:] = -1
    order = np.argsort(np.where(idx < 0, np.iinfo(np.int32).max, bags),
                       kind="stable")
    bags_s = np.where(idx[order] < 0, -1, bags[order])
    idx_s = idx[order]
    got = embedding_bag_pallas(table, jnp.asarray(idx_s), jnp.asarray(bags_s),
                               n_bags, be=32, bw=16, interpret=True)
    want = ref.embedding_bag_ref(table, jnp.asarray(idx_s), jnp.asarray(bags_s),
                                 n_bags)
    visited = np.zeros(n_bags, bool)
    for s in bags_s[bags_s >= 0]:
        lo = (s // 16) * 16
        visited[lo:lo + 16] = True
    np.testing.assert_allclose(np.asarray(got)[visited],
                               np.asarray(want)[visited], rtol=1e-5, atol=1e-4)


# -------------------------------------------------------------- lsh hash ----
@pytest.mark.parametrize("n,d,L,m", [(64, 8, 2, 4), (300, 32, 4, 8), (128, 128, 1, 2)])
def test_lsh_hash_kernel(n, d, L, m):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    proj = jnp.asarray(rng.normal(size=(L, m, d)), jnp.float32)
    bias = jnp.asarray(rng.uniform(0, 1, size=(L, m)), jnp.float32)
    got = lsh_hash_pallas(x, proj, bias, 0.8, bn=32, interpret=True)
    want = ref.lsh_hash_ref(x, proj, bias, 0.8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lsh_hash_matches_pstable_module():
    """The kernel must agree with the production LSH used by CIVS."""
    from repro.lsh.pstable import hash_points
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(50, 16)), jnp.float32)
    proj = jnp.asarray(rng.normal(size=(3, 4, 16)), jnp.float32)
    bias = jnp.asarray(rng.uniform(0, 2, size=(3, 4)), jnp.float32)
    got = lsh_hash_pallas(x, proj, bias, 2.0, bn=16, interpret=True)
    want = np.asarray(hash_points(x, proj, bias, 2.0)).T  # (L,n) -> (n,L)
    got_u = np.asarray(got).astype(np.uint32)
    np.testing.assert_array_equal(got_u, want.astype(np.uint32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lsh_hash_dtype_bit_parity(dtype):
    """Cross-dtype bit parity of the f32-cast hashing convention: for any
    input dtype, `pstable.hash_points`, the jnp oracle, and the Pallas
    kernel must produce IDENTICAL keys — ShardedStore/StreamedStore key
    identity (and thus streamed/sharded retrieval parity) depends on it.
    The einsum used to run in the input dtype while the kernel cast to f32;
    the f32-cast convention is now shared."""
    from repro.lsh.pstable import hash_points
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(64, 12)), dtype)
    proj = jnp.asarray(rng.normal(size=(2, 4, 12)), dtype)
    bias = jnp.asarray(rng.uniform(0, 1, size=(2, 4)), dtype)
    want = np.asarray(ref.lsh_hash_ref(x, proj, bias, 0.7)).astype(np.uint32)
    via_pstable = np.asarray(hash_points(x, proj, bias, 0.7)).T
    via_kernel = np.asarray(
        lsh_hash_pallas(x, proj, bias, 0.7, bn=32, interpret=True)
    ).astype(np.uint32)
    np.testing.assert_array_equal(via_pstable, want)
    np.testing.assert_array_equal(via_kernel, want)


# ----------------------------------------- padded-tail poison contracts ----
# The scenarios live in repro.analysis.contracts (the CI gate runs them as
# `python -m repro.analysis.check`); parametrizing over the same registry
# here keeps the pytest tier and the gate bit-for-bit in sync.
from repro.analysis.contracts import POISON_BACKENDS, POISON_CHECKS


@pytest.mark.parametrize("backend", POISON_BACKENDS)
@pytest.mark.parametrize("scenario", sorted(POISON_CHECKS))
def test_padded_tail_poison_contract(scenario, backend):
    """NaN/Inf-poison the pad regions of every fused kernel and assert the
    valid-slot outputs are BIT-identical to a zero-padded baseline (and the
    pad-slot outputs honor their documented sentinel: label -1, score 0,
    valid_out False, neg -inf, ...)."""
    problem = POISON_CHECKS[scenario](backend)
    assert problem is None, f"{scenario} [{backend}]: {problem}"
