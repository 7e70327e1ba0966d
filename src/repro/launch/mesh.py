"""Production mesh builders. Defined as FUNCTIONS so importing this module
never touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init).

Target: TPU v5e pods — 16x16 (256 chips) per pod; the multi-pod mesh adds a
leading "pod" axis over DCN. Axis conventions in DESIGN.md §4.
"""

from __future__ import annotations

from repro.distributed.context import MeshContext, make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_context(*, multi_pod: bool = False, fsdp: bool = True) -> MeshContext:
    mesh = make_production_mesh(multi_pod=multi_pod)
    data_axes = ("pod", "data") if multi_pod else ("data",)
    return MeshContext(mesh=mesh, data_axes=data_axes, model_axis="model",
                       fsdp=fsdp)


def make_small_context(n_data: int = 4, n_model: int = 2) -> MeshContext:
    """Reduced mesh for subprocess tests (8 host devices)."""
    mesh = make_mesh((n_data, n_model), ("data", "model"))
    return MeshContext(mesh=mesh, data_axes=("data",), model_axis="model")

