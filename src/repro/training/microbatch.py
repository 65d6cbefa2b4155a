"""Micro-batched gradient accumulation — the paper's equivalence primitive.

C2P2SL splits each batch into k micro-batches and accumulates gradients; the
paper asserts (SII-C, last paragraph) that the accumulated update is
mathematically equivalent to the full-batch computation.  This module is
that statement as code, and tests/test_equivalence.py asserts it to float
tolerance for every model family.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def split_batch(batch, k: int):
    """Reshape every leaf [B, ...] -> [k, B//k, ...]."""
    def r(x):
        b = x.shape[0]
        assert b % k == 0, f"batch {b} not divisible by k={k}"
        return x.reshape((k, b // k) + x.shape[1:])
    return jax.tree.map(r, batch)


def microbatched_value_and_grad(loss_fn, k: int):
    """value_and_grad with gradient accumulation over k micro-batches.

    ``loss_fn(params, micro_batch) -> (loss, metrics)``.  Returns a function
    ``(params, batch) -> ((loss, metrics), grads)`` where loss/metrics/grads
    are averaged over micro-batches (identical semantics to full batch when
    the loss is a per-sample mean).
    """
    vg = jax.value_and_grad(loss_fn, has_aux=True)

    if k <= 1:
        return lambda params, batch: vg(params, batch)

    def run(params, batch):
        micro = split_batch(batch, k)

        def body(carry, mb):
            (loss, mets), grads = vg(params, mb)
            acc_loss, acc_mets, acc_grads = carry
            with jax.named_scope("grad_accum"):
                acc = jax.tree.map(jnp.add, acc_grads, grads)
            mets_sum = jax.tree.map(jnp.add, acc_mets, mets)
            return (acc_loss + loss, mets_sum, acc), None

        zero_like = lambda t: jax.tree.map(
            lambda x: jnp.zeros(x.shape, x.dtype), t)
        # peek structure with eval_shape (no compute)
        (l0, m0), g0 = jax.eval_shape(vg, params,
                                      jax.tree.map(lambda x: x[0], micro))
        init = (jnp.zeros(l0.shape, l0.dtype), zero_like(m0), zero_like(g0))
        (loss, mets, grads), _ = jax.lax.scan(body, init, micro)
        inv = 1.0 / k
        return ((loss * inv, jax.tree.map(lambda x: x * inv, mets)),
                jax.tree.map(lambda g: g * inv, grads))

    return run
