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
    return vary_over(val, jax.typeof(ref).vma)


def vary_over(val, vma):
    """``val`` marked varying over the manual axes ``vma`` it does not
    vary over yet."""
    missing = tuple(sorted(set(vma) - set(jax.typeof(val).vma)))
    if missing:
        return jax.lax.pcast(val, missing, to="varying")
    return val


def vma_unchecked():
    """A context in which varying-manual-axes types are not checked, so
    a ``pallas_call`` whose ``out_shape`` names no ``vma`` (as the
    splash-attention kernels' do) traces inside a ``check_vma=True``
    ``shard_map``; its outputs are typed unvarying.  They are typed back
    with ``vary_over`` where nothing differentiates the cast
    (``kernels/causal_attention.py``)."""
    from jax._src import config
    return config._check_vma(False)


def splash_forward(kern, q, k, v):
    """(out, lse): the forward of the splash-attention kernel ``kern``
    (``make_splash_mha``/``make_splash_mqa`` with a mask computed in the
    kernel, ``mask_function``) on one sequence, with its log-sum-exp.
    Through JAX's private entry point, so that the caller may put it
    and ``splash_backward`` under a ``custom_vjp`` of its own; such a
    mask has no partial-mask blocks for the public entry to reshape."""
    splash = _splash()
    kw = kern.kwargs
    out, (lse,) = splash._splash_attention_forward(
        kern.fwd_mask_info, q, k, v, None, None,
        mask_value=kw["mask_value"], is_mqa=kw["is_mqa"],
        block_sizes=kw["block_sizes"], residual_checkpoint_name=None,
        save_residuals=True, mask_function=kw["mask_function"],
        attn_logits_soft_cap=kw["attn_logits_soft_cap"],
        interpret=kw["interpret"])
    return out, lse


def splash_backward(kern, q, k, v, out, lse, do):
    """(dq, dk, dv): the dQ and dKV kernels of ``kern`` for the
    cotangent ``do`` of ``splash_forward``'s ``out``."""
    splash = _splash()
    kw = kern.kwargs
    # the residuals as the kernel's forward rule saves them: q, k, v,
    # segment ids, sinks, out, lse and the dQ and dKV mask infos
    res = (q, k, v, None, None, out, lse, kern.dq_mask_info,
           kern.dkv_mask_info)
    grads = splash._splash_attention_bwd(
        save_residuals=False, mask_value=kw["mask_value"],
        is_mqa=kw["is_mqa"], block_sizes=kw["block_sizes"],
        residual_checkpoint_name=None, mask_function=kw["mask_function"],
        attn_logits_soft_cap=kw["attn_logits_soft_cap"],
        interpret=kw["interpret"], res=res, do=do)
    return grads[3:6]                   # after the three mask infos


def _splash():
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel)
    return splash_attention_kernel


def auto_axes_sharding(mesh, manual_axes, spec):
    """A NamedSharding usable for ``with_sharding_constraint`` INSIDE a
    partial-manual region: the mesh view has ``manual_axes`` Manual and
    everything else Auto."""
    AxisType = jax.sharding.AxisType
    abs_mesh = mesh.abstract_mesh.update(axis_types=tuple(
        AxisType.Manual if n in manual_axes else AxisType.Auto
        for n in mesh.shape))
    return NamedSharding(abs_mesh, spec)
