"""Causal self-attention as blocked Pallas kernels, forward and backward.

Built on the splash-attention kernels that ship with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``): a forward kernel
that keeps each [block_q, block_kv] score tile, the running max and sum
and the output accumulator in VMEM, and dQ and dKV kernels that
recompute the tiles from the forward's log-sum-exp.  Its ``custom_vjp``
is the derivative, so XLA never sees an [S, S] score tensor.  A
``CausalMask`` per head lets the kernels skip every block above the
diagonal, its DMA included; the blocks on the diagonal are masked by
index inside the tile.

Precision: the QK matmuls and the backward's take operands in the
inputs' dtype (bf16 in the models) with f32 accumulation; the forward's
PV matmul takes the f32 probabilities and v widened to f32, at Mosaic's
default precision (not ``HIGHEST``), as XLA takes an f32 einsum.  The
scores, the softmax statistics and every accumulator are f32.

Layout: q [B, S, Hq, dh], k and v [B, S, Hkv, dh], the models' own.
MHA (Hq == Hkv) maps the batch over one multi-head kernel; GQA maps the
batch and the KV heads over a multi-query kernel that serves each KV
head's group of query heads.  S and dh must be multiples of ``BLOCK``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _splash, splash_attention_mask as _mask)

from repro.parallel.compat import (splash_backward, splash_forward,
                                   vary_over, vma_unchecked)

#: the kernel takes sequences and head widths that are multiples of this
BLOCK = 128
#: the tiles tried, largest first: every pass (the forward, dQ and dKV)
#: takes the largest that divides S
BLOCKS = (512, 256, 128)


def block_sizes(s: int) -> _splash.BlockSizes:
    """The kernels' tiles for a sequence of ``s`` (a multiple of
    ``BLOCK``)."""
    b = next(b for b in BLOCKS if s % b == 0)
    return _splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        block_q_dq=b, block_kv_dq=b)


def _kernel(heads: int, s: int, mqa: bool, interpret: bool):
    """The splash kernel of one sequence, its mask's block info concrete
    arrays (not tracers of the program being traced), so that the
    backward, traced later, may close over them."""
    mask = _mask.MultiHeadMask([_mask.CausalMask((s, s))] * heads)
    make = _splash.make_splash_mqa if mqa else _splash.make_splash_mha
    with jax.ensure_compile_time_eval():
        return make(mask, block_sizes=block_sizes(s), head_shards=1,
                    q_seq_shards=1, interpret=interpret)


def _attend(kern, batch_dims: int, q, k, v):
    """The splash kernel ``kern`` (one sequence) mapped over the leading
    ``batch_dims`` dimensions of q, k and v, with its forward and its
    dQ/dKV kernels under a ``custom_vjp`` of this module's own (the
    kernels' entry points are JAX's private ones, behind
    ``parallel/compat.py``).

    Inside the pipeline's ``shard_map`` (Manual over ``pod``, varying
    types checked) the splash kernels cannot trace as splash's own
    ``custom_vjp`` calls them: their ``out_shape`` names no ``vma``.  So
    each kernel is traced with the check off (``vma_unchecked``), and
    its results, typed unvarying, are cast to the inputs' varying axes
    in this ``custom_vjp``'s forward and backward, where nothing
    transposes the cast into a ``psum`` over the stages.  The residuals
    (q, k, v, the output and its log-sum-exp) are all so typed, so the
    ``shard_map`` keeps each stage's own.  Outside a ``shard_map`` every
    varying set is empty and the casts are no-ops.
    """
    vma = frozenset().union(*(jax.typeof(x).vma for x in (q, k, v)))

    def batched(f):
        for _ in range(batch_dims):
            f = jax.vmap(f)
        return f

    @jax.custom_vjp
    def attend(q, k, v):
        return fwd(q, k, v)[0]

    def fwd(q, k, v):
        with vma_unchecked():
            out, lse = batched(partial(splash_forward, kern))(q, k, v)
        out, lse = vary_over(out, vma), vary_over(lse, vma)
        return out, (q, k, v, out, lse)

    def bwd(res, do):
        with vma_unchecked():
            grads = batched(partial(splash_backward, kern))(*res, do)
        return tuple(vary_over(d, vma) for d in grads)

    attend.defvjp(fwd, bwd)
    return attend(q, k, v)


def causal_attention(q, k, v, *, interpret: bool = False):
    """softmax(q kᵀ/√dh + causal) v: q [B, S, Hq, dh], k/v [B, S, Hkv,
    dh] -> [B, S, Hq, dh] in q's dtype.  Query i attends to keys 0..i of
    its own sequence: the mask is by index, so the caller's positions
    must be ``arange(S)``."""
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    if (s % BLOCK or dh % BLOCK or hq % hkv or k.shape[1] != s
            or v.shape != k.shape or k.shape[-1] != dh):
        raise ValueError(f"causal_attention takes S and dh in multiples of "
                         f"{BLOCK}, Hq a multiple of Hkv and k, v alike: "
                         f"q {q.shape}, k {k.shape}, v {v.shape}")
    dt = q.dtype
    # the kernel takes q pre-scaled: one rounding of q / √dh to q's dtype
    qt = (jnp.swapaxes(q, 1, 2).astype(jnp.float32)
          * (1.0 / math.sqrt(dh))).astype(dt)                 # [B,Hq,S,dh]
    kt = jnp.swapaxes(k, 1, 2).astype(dt)                     # [B,Hkv,S,dh]
    vt = jnp.swapaxes(v, 1, 2).astype(dt)
    if hq == hkv:                           # MHA: one kernel over B
        out = _attend(_kernel(hq, s, False, interpret), 1, qt, kt, vt)
    else:                                   # GQA: one per KV head, over B
        rep = hq // hkv
        out = _attend(_kernel(rep, s, True, interpret), 2,
                      qt.reshape(b, hkv, rep, s, dh), kt, vt)
    out = out.reshape(b, hq, s, dh)
    return jnp.swapaxes(out, 1, 2)
