"""Global mesh context: model code asks "what mesh am I lowering for?"
instead of threading a mesh through every call. Set by the trainer, server,
dry-run launcher, and tests. When no context is set, models take their pure
single-device paths (no collectives) — that is what CPU smoke tests use.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
              devices=None) -> Mesh:
    """`jax.make_mesh` with every axis `Auto`. Since jax 0.8 the default is
    `Explicit`, under which an array sharded by one jit (the mesh map
    phase) cannot be consumed by a jit that states no mesh (the claim
    reducer's scatter); this code base shards by `NamedSharding`s and
    `shard_map`, which is what `Auto` axes mean."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


@dataclass(frozen=True)
class MeshContext:
    mesh: Mesh
    # axis-name conventions (see DESIGN.md §4):
    #   batch/tokens/edges/seeds shard over data_axes (("pod","data") multi-pod)
    #   heads/mlp/vocab/experts shard over model_axis
    data_axes: tuple[str, ...] = ("data",)
    model_axis: str = "model"
    fsdp: bool = True   # ZeRO-3: params themselves sharded over data axes too

    @property
    def n_data(self) -> int:
        return int(
            __import__("math").prod(self.mesh.shape[a] for a in self.data_axes))

    @property
    def n_model(self) -> int:
        return int(self.mesh.shape[self.model_axis])


_CTX: Optional[MeshContext] = None


def set_mesh_context(ctx: Optional[MeshContext]) -> None:
    global _CTX
    _CTX = ctx


def get_mesh_context() -> Optional[MeshContext]:
    return _CTX


@contextlib.contextmanager
def mesh_context(ctx: Optional[MeshContext]):
    prev = get_mesh_context()
    set_mesh_context(ctx)
    try:
        yield ctx
    finally:
        set_mesh_context(prev)


def data_axes() -> tuple[str, ...] | None:
    ctx = get_mesh_context()
    return ctx.data_axes if ctx else None


def model_axis() -> str | None:
    ctx = get_mesh_context()
    return ctx.model_axis if ctx else None
