"""Roofline analysis from the dry-run artifacts (EXPERIMENTS.md §Roofline).

Three terms per (arch x shape), single-pod 16x16 = 256 chips (v5e), with
the chip's published peaks from `DEVICE_PEAKS`:

  compute    = HLO_FLOPs_global / (256 * flops_bf16)      [s]
  memory     = HLO_bytes_global / (256 * hbm_bytes_s)     [s]
  collective = collective_bytes_per_chip / ici_link_bytes_s [s]

Sources: HLO_FLOPs/bytes come from the UNROLLED cost-probe lowering (XLA's
cost analysis counts while bodies once; the probe has no loops). Collective
bytes come from the trip-count-multiplied census over the compiled partitioned
HLO (per-chip program; all-reduce counted 2x; single-link conservative
convention). MODEL_FLOPS is the analytic useful-work count: 6*N*D train /
2*N*D forward (N = active params for MoE); op-count formulas for GNN/recsys.

  PYTHONPATH=src python -m benchmarks.roofline [--mesh single] [--md out.md]
"""

from __future__ import annotations

import argparse
import glob
import json
import os

CHIPS = {"single": 256, "multi": 512}

# Published per-chip peaks, keyed by `jax.Device.device_kind`. Source: Google
# Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect over 4 links (50 GB/s
# per link).
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                    "ici_link_bytes_s": 50e9},
}
# the chip the dry-run's production meshes are built for
DRYRUN_KIND = "TPU v5 lite"


def peaks(kind: str) -> dict:
    """The published peaks of one chip of `kind`; an unknown kind is an
    error, never a default."""
    if kind not in DEVICE_PEAKS:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(DEVICE_PEAKS)}")
    return DEVICE_PEAKS[kind]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(REPO, "experiments", "dryrun")


# ------------------------------------------------- analytic MODEL_FLOPS ----
def _lm_model_flops(arch: str, shape: str) -> float:
    import jax
    from repro.configs import get_arch
    cfg = get_arch(arch).CONFIG
    n_active = cfg.active_param_count()
    shapes = {"train_4k": (256, 4096, "train"), "prefill_32k": (32, 32768, "fwd"),
              "decode_32k": (128, 1, "fwd"), "long_500k": (1, 1, "fwd")}
    b, s, kind = shapes[shape]
    tokens = b * s
    return (6.0 if kind == "train" else 2.0) * n_active * tokens


def _mlp_flops(sizes) -> float:
    return sum(2.0 * a * b for a, b in zip(sizes[:-1], sizes[1:]))


def _gnn_model_flops(arch: str, shape: str) -> float:
    from repro.configs import get_arch
    from repro.configs.registry import GNN_SHAPES
    mod = get_arch(arch)
    cell = mod.make_cell(shape)
    cfg = cell.model_cfg
    sp = GNN_SHAPES[shape]
    if shape == "molecule":
        n = sp["batch"] * sp["n_nodes"]
        e = sp["batch"] * sp["n_edges"]
    elif shape == "minibatch_lg":
        n = sp["batch_nodes"] * (1 + sp["fanout"][0] * (1 + sp["fanout"][1]))
        e = sp["batch_nodes"] * sp["fanout"][0] * (1 + sp["fanout"][1])
    else:
        n, e = sp["n_nodes"], sp["n_edges"]
    d = cfg.d_hidden
    enc = n * _mlp_flops((sp["d_feat"], d, d))
    dec = n * _mlp_flops((d, d, cfg.n_out))
    if cfg.kind in ("mgn", "graphcast"):
        per_layer = (e * _mlp_flops((3 * d, d, d)) + n * _mlp_flops((2 * d, d, d))
                     + e * d * 2)
        enc += e * _mlp_flops((cfg.d_edge_in, d, d))
    elif cfg.kind == "gin":
        per_layer = n * _mlp_flops((d, d, d)) + e * d * 2
    else:  # sage
        per_layer = n * (2 * d * d * 2) + e * d * 2
    fwd = enc + cfg.n_layers * per_layer + dec
    return 3.0 * fwd  # train step ~ fwd + 2x bwd


def _bst_model_flops(shape: str) -> float:
    from repro.configs import get_arch
    from repro.configs.registry import RECSYS_SHAPES
    cfg = get_arch("bst").CONFIG
    sp = RECSYS_SHAPES[shape]
    b = sp.get("n_candidates", sp["batch"])
    s1 = cfg.seq_len + 1
    d = cfg.embed_dim
    blk = s1 * (4 * 2 * d * d) + 2 * 2 * s1 * s1 * d + s1 * _mlp_flops((d, 4 * d, d))
    d_flat = s1 * d + cfg.n_dense + cfg.n_multi * d
    mlp = _mlp_flops((d_flat,) + tuple(cfg.mlp) + (1,))
    fwd = b * (cfg.n_blocks * blk + mlp)
    return (3.0 if sp["step"] == "train" else 1.0) * fwd


def model_flops(arch: str, shape: str, kind: str) -> float:
    if kind == "lm":
        return _lm_model_flops(arch, shape)
    if kind == "gnn":
        return _gnn_model_flops(arch, shape)
    return _bst_model_flops(shape)


# ------------------------------------------------------------- the table ----
def build_rows(mesh: str = "single") -> list[dict]:
    chips = CHIPS[mesh]
    pk = peaks(DRYRUN_KIND)
    rows = []
    for path in sorted(glob.glob(os.path.join(ART, f"*__{mesh}.json"))):
        d = json.load(open(path))
        if d["status"] == "skipped":
            rows.append({"cell": d["cell_id"], "status": "skipped",
                         "note": d["skip_reason"].split(":")[0]})
            continue
        if d["status"] != "ok":
            rows.append({"cell": d["cell_id"], "status": "error"})
            continue
        arch, shape = d["arch"], d["shape"]
        kind = ("lm" if any(a in arch for a in
                            ("gemma", "deepseek", "danube", "llama", "kimi"))
                else ("recsys" if arch == "bst" else "gnn"))
        flops_g = d.get("probe_flops_global") or (
            d.get("flops_per_device", 0.0) * chips)
        bytes_g = d.get("probe_bytes_global") or (
            d.get("bytes_per_device", 0.0) * chips)
        coll = d.get("collectives", {}).get("total_bytes", 0)
        t_comp = flops_g / (chips * pk["flops_bf16"])
        t_mem = bytes_g / (chips * pk["hbm_bytes_s"])
        t_coll = coll / pk["ici_link_bytes_s"]
        mf = model_flops(arch, shape, kind)
        terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
        dom = max(terms, key=terms.get)
        bound = max(terms.values())
        rows.append({
            "cell": d["cell_id"], "status": "ok", "kind": kind,
            "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
            "dominant": dom,
            "model_flops": mf, "hlo_flops": flops_g,
            "useful_ratio": (mf / flops_g) if flops_g else 0.0,
            "roofline_frac": (t_comp / bound) if bound else 0.0,
            "mem_gb_per_dev": (d.get("memory_analysis", {}).get("temp_size_in_bytes", 0)
                               + d.get("memory_analysis", {}).get("argument_size_in_bytes", 0)) / 1e9,
        })
    return rows


def to_markdown(rows: list[dict], mesh: str) -> str:
    chips = CHIPS[mesh]
    pk = peaks(DRYRUN_KIND)
    out = [f"### Roofline — {mesh} pod ({chips} chips, {DRYRUN_KIND}: "
           f"{pk['flops_bf16'] / 1e12:.0f} TF/s bf16, "
           f"{pk['hbm_bytes_s'] / 1e9:.0f} GB/s HBM, "
           f"{pk['ici_link_bytes_s'] / 1e9:.0f} GB/s link)",
           "",
           "| cell | compute s | memory s | collective s | dominant | "
           "MODEL/HLO flops | roofline frac | HBM GB/dev |",
           "|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if r["status"] != "ok":
            out.append(f"| {r['cell']} | — | — | — | {r.get('note', r['status'])} "
                       "| — | — | — |")
            continue
        out.append(
            f"| {r['cell']} | {r['t_compute_s']:.3e} | {r['t_memory_s']:.3e} "
            f"| {r['t_collective_s']:.3e} | **{r['dominant']}** "
            f"| {r['useful_ratio']:.2f} | {r['roofline_frac']:.2f} "
            f"| {r['mem_gb_per_dev']:.1f} |")
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--md", default=os.path.join(REPO, "experiments",
                                                 "roofline.md"))
    args = ap.parse_args()
    rows = build_rows(args.mesh)
    md = to_markdown(rows, args.mesh)
    print(md)
    with open(args.md, "w") as f:
        f.write(md + "\n")
    with open(args.md.replace(".md", ".json"), "w") as f:
        json.dump(rows, f, indent=1)
    print(f"\n[roofline] wrote {args.md}")


if __name__ == "__main__":
    main()
