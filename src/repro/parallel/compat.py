"""The parallel runtime's JAX surface: meshes, shard_map, varying marks.

The repo runs on one JAX generation, the explicit-sharding one (top-level
``jax.shard_map`` with ``axis_names``/``check_vma``, typed meshes,
``jax.set_mesh``, ``jax.lax.pcast``); docs/compat.md names the installed
version.  Model, launch and analysis code import these helpers and the
sharding types from here, so an API move touches one file.

Nothing here touches device state at import (the dry-run sets XLA_FLAGS
before any jax init).
"""
from __future__ import annotations

import jax
# Re-exported so parallel/launch modules have one import site for sharding
# types.
from jax.sharding import Mesh, NamedSharding, PartitionSpec  # noqa: F401


def _auto(n: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n


def make_mesh(axis_shapes, axis_names, *, axis_types=None, devices=None):
    """``jax.make_mesh`` with all-Auto axis types unless ``axis_types``
    says otherwise."""
    shapes = tuple(axis_shapes)
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shapes, tuple(axis_names),
                         axis_types=axis_types or _auto(len(shapes)),
                         **kwargs)


def abstract_mesh(axis_shapes, axis_names, *, axis_types=None):
    """Device-free mesh for spec/feasibility math and jaxpr tracing."""
    shapes = tuple(axis_shapes)
    return jax.sharding.AbstractMesh(
        shapes, tuple(axis_names), axis_types=axis_types or _auto(len(shapes)))


def mesh_context(mesh):
    """Make ``mesh`` ambient for spec-only sharding annotations."""
    return jax.set_mesh(mesh)


def shard_map(f, mesh, in_specs, out_specs, *, manual_axes=None,
              check: bool = False):
    """``jax.shard_map``, Manual over ``manual_axes`` (None: every mesh
    axis) with GSPMD-auto on the rest; ``check`` is ``check_vma``."""
    kwargs = {"check_vma": check}
    if manual_axes is not None:
        kwargs["axis_names"] = set(manual_axes)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def mark_varying(x, axes):
    """Mark ``x`` as varying over the manual ``axes``."""
    return jax.lax.pcast(x, tuple(axes), to="varying")


def match_vma(val, ref):
    """Give ``val`` (e.g. a freshly-created scan carry) the
    varying-manual-axes of ``ref`` — inside a partial-manual shard_map a
    zero-initialized carry is otherwise 'unvarying' and scan rejects the
    carry-type mismatch."""
    missing = tuple(sorted(set(jax.typeof(ref).vma)
                           - set(jax.typeof(val).vma)))
    if missing:
        return jax.lax.pcast(val, missing, to="varying")
    return val


def auto_axes_sharding(mesh, manual_axes, spec):
    """A NamedSharding usable for ``with_sharding_constraint`` INSIDE a
    partial-manual region: the mesh view has ``manual_axes`` Manual and
    everything else Auto."""
    AxisType = jax.sharding.AxisType
    abs_mesh = mesh.abstract_mesh.update(axis_types=tuple(
        AxisType.Manual if n in manual_axes else AxisType.Auto
        for n in mesh.shape))
    return NamedSharding(abs_mesh, spec)
