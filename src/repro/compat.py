"""The repo's one spelling of the jax mesh/sharding calls (jax 0.9).

    make_mesh(shape, axes)   — jax.make_mesh with Auto axis types
    mesh_context(mesh)       — jax.set_mesh(mesh) (ambient mesh for jit)
    shard_map(f, mesh=, in_specs=, out_specs=)
                             — jax.shard_map with check_vma=False
    cost_analysis(compiled)  — compiled.cost_analysis() as a dict
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types on every axis."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def mesh_context(mesh):
    """Context manager installing `mesh` as the ambient mesh for jit."""
    return jax.set_mesh(mesh)


def cost_analysis(compiled) -> dict:
    """`compiled.cost_analysis()` (a flat dict of cost counters)."""
    return compiled.cost_analysis()


def shard_map(f, *, mesh, in_specs, out_specs):
    """Per-shard mapping without replication checking (our bodies psum
    explicitly where needed; the decode bodies are embarrassingly
    parallel)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
