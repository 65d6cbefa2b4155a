"""Slot-based KV / recurrent-state cache arena for continuous batching.

The decode state of every model family (KV buffers, local-window ring
buffers, rglru recurrent states, rwkv shift/state tensors) is a pytree
whose leaves all carry ONE batch axis — but not the SAME axis: a
homogeneous scan-stacked cache puts layers first (``[L, B, cache_len,
...]``), a heterogeneous tuple-of-dicts cache puts batch first.  The
arena treats that axis as the SLOT axis: a fixed-shape
``[.., slots, ..]`` arena that requests are written into when admitted
and freed from when they complete, so the decode step stays one jitted
fixed-shape program while requests join and leave at arbitrary steps.

``slot_axes`` discovers the per-leaf slot axis structurally (two
``eval_shape`` probes at coprime batch sizes — the axis that moved is
the batch axis), so the arena works for every family without a
per-model axis table.  ``take_slot``/``put_slot`` are pure jax functions
of ``(tree, axes)``; the engine jits them once, ``put_slot`` with the
arena donated, so admitting a request writes its one row in place.  The
decode step does not go through these helpers: the model's batched
``decode_step`` writes one token per lane per layer straight into the
donated arena.  ``FreeList`` is the host-side slot allocator.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Probe batch sizes for slot-axis discovery.  Coprime and unequal to any
# plausible static cache dimension pair ratio — the ONLY leaf axis that
# differs between the two probes is the batch axis.
_PROBE_A, _PROBE_B = 5, 7


def slot_axes(model, cache_len: int, cache_dtype=jnp.float32):
    """Per-leaf slot (batch) axis of ``model.init_cache``'s pytree.

    Returns a pytree of ints with the same structure as the cache.
    Structural, not positional: two ``eval_shape`` probes at batch sizes
    5 and 7 — the unique axis whose extent changed is the batch axis.
    """
    a = jax.eval_shape(lambda: model.init_cache(_PROBE_A, cache_len,
                                                cache_dtype))
    b = jax.eval_shape(lambda: model.init_cache(_PROBE_B, cache_len,
                                                cache_dtype))

    def one(x, y):
        diff = [i for i, (p, q) in enumerate(zip(x.shape, y.shape))
                if p != q]
        if len(diff) != 1:
            raise ValueError(
                f"cache leaf {x.shape} -> {y.shape} has no unique batch "
                f"axis (changed axes: {diff}); the slot arena needs "
                "exactly one per leaf")
        return diff[0]

    return jax.tree.map(one, a, b)


def take_slot(tree, axes, index):
    """Index one slot out of an arena (or one row out of a prefill
    batch): every leaf loses its slot axis.  ``index`` may be traced."""
    return jax.tree.map(
        lambda a, ax: jax.lax.dynamic_index_in_dim(a, index, ax,
                                                   keepdims=False),
        tree, axes)


def put_slot(tree, axes, row, index):
    """Write a slot-axis-free ``row`` (from ``take_slot``) into slot
    ``index`` of the arena.  ``index`` may be traced."""
    return jax.tree.map(
        lambda a, r, ax: jax.lax.dynamic_update_index_in_dim(
            a, r.astype(a.dtype), index, ax),
        tree, row, axes)


class FreeList:
    """Host-side slot allocator: LIFO free list over ``n`` slots.

    LIFO on purpose — a freed slot is re-used as soon as possible, which
    is exactly the reuse pattern the continuous-batching equivalence
    tests pin (a stale cache row must never leak into the next tenant).
    """

    def __init__(self, n: int):
        if n < 1:
            raise ValueError(f"FreeList needs >= 1 slot, got {n}")
        self.n = int(n)
        self._free = list(range(self.n - 1, -1, -1))   # pop() -> slot 0 first

    def __len__(self):
        return len(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise IndexError("no free slots")
        return self._free.pop()

    def free(self, slot: int) -> None:
        slot = int(slot)
        if not 0 <= slot < self.n:
            raise ValueError(f"slot {slot} out of range [0, {self.n})")
        if slot in self._free:
            raise ValueError(f"slot {slot} double-freed")
        self._free.append(slot)
