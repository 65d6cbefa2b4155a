"""Production mesh construction.

A function, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init).
"""
from __future__ import annotations

import jax

from repro.parallel.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 1):
    """Small mesh over whatever local devices exist (tests, examples)."""
    n = len(jax.devices())
    want = data * model * pod
    assert want <= n, f"need {want} devices, have {n}"
    if pod > 1:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
