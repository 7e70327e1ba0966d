"""Published per-chip peaks, keyed by `jax.Device.device_kind`.

Source: Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect. Copied from the program's `benchmarks/roofline.py`
`DEVICE_PEAKS`.
"""

from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(kind: str) -> dict:
    """The published peaks of one chip of `kind`; an unknown kind is an
    error, never a default."""
    if kind not in DEVICE_PEAKS:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(DEVICE_PEAKS)}")
    return DEVICE_PEAKS[kind]


def roofline_s(flops: float, hbm_bytes: float, pk: dict) -> tuple[float,
                                                                   str]:
    """The least time the chip needs for the work, and which bound sets
    it. f32 contractions at full precision are counted once against the
    bf16 peak."""
    t_comp = flops / pk["flops_bf16"]
    t_mem = hbm_bytes / pk["hbm_bytes_s"]
    return (t_comp, "compute") if t_comp >= t_mem else (t_mem, "memory")
