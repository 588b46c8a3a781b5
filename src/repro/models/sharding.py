"""Logical-axis sharding: maps layer-semantic axes onto the mesh.

Logical names used by parameter/activation definitions:
  'fsdp'  -> the data-parallel axes (('pod','data') multi-pod, ('data',)
             single-pod): ZeRO-3 style parameter sharding
  'tp'    -> the tensor-parallel 'model' axis (heads / d_ff / experts)
  'seq'   -> sequence sharding for long-context decode caches
  None    -> replicated
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    dp: tuple = ("data",)
    tp: str = "model"

    def resolve(self, logical) -> P:
        out = []
        for name in logical:
            if name == "fsdp":
                out.append(self.dp if len(self.dp) > 1 else self.dp[0])
            elif name == "tp":
                out.append(self.tp)
            elif name == "seq":
                out.append(self.tp)  # decode caches: shard sequence over tp
            elif name == "dp+tp":
                out.append(tuple(self.dp) + (self.tp,))
            elif name is None:
                out.append(None)
            else:
                raise ValueError(f"unknown logical axis {name!r}")
        return P(*out)

    def batch(self) -> P:
        return P(self.dp if len(self.dp) > 1 else self.dp[0])


def axes_for_mesh(mesh) -> MeshAxes:
    names = mesh.axis_names
    dp = tuple(n for n in names if n in ("pod", "data"))
    return MeshAxes(dp=dp or ("data",), tp="model")


def constrain(x, axes: MeshAxes, logical):
    """with_sharding_constraint by logical names (no-op without a mesh)."""
    try:
        return jax.lax.with_sharding_constraint(x, axes.resolve(logical))
    except (ValueError, RuntimeError):
        return x


def named_sharding(mesh, axes: MeshAxes, logical) -> NamedSharding:
    return NamedSharding(mesh, axes.resolve(logical))


def _axis_size(mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            n *= mesh.shape[a]
        return n
    return mesh.shape[entry]


def shape_safe_spec(mesh, spec: P, shape) -> P:
    """Drop spec axes that do not evenly divide the dimension (jit input
    shardings require even tiling; e.g. batch=1 long-context decode leaves
    the data axis idle, odd vocabs fall back to replicated)."""
    out = []
    for entry, dim in zip(tuple(spec), shape):
        out.append(entry if dim % _axis_size(mesh, entry) == 0 else None)
    return P(*out)


def safe_named_sharding(mesh, axes: MeshAxes, logical, shape) -> NamedSharding:
    return NamedSharding(mesh, shape_safe_spec(mesh, axes.resolve(logical), shape))
