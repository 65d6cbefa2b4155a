"""Jit'd public wrappers around the Pallas kernels.

On a TPU backend the kernels compile to Mosaic.  On the CPU backend (the
test suite) they run in ``interpret=True`` mode — the kernel body
executes as traced JAX ops, which is what the tests validate against the
``ref.py`` oracles.  Any other backend is an error, not a silent
interpreter.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import causal_attention as _ca
from repro.kernels import flash_attention as _fa
from repro.kernels import moe_gmm as _gmm
from repro.kernels import rglru as _rglru
from repro.kernels import rwkv6 as _rwkv6
from repro.kernels import wire_codec as _wc


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels compile for 'tpu' and interpret on 'cpu'; "
            f"backend {backend!r} has neither path")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0):
    """q [B,Hq,S,dh], k/v [B,Hkv,S,dh] -> [B,Hq,S,dh]."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               interpret=_interpret())


@jax.jit
def causal_attention(q, k, v):
    """q [B,S,Hq,dh], k/v [B,S,Hkv,dh] -> [B,S,Hq,dh]: blocked causal
    self-attention, forward and backward kernels; S and dh multiples of
    128; positions must be ``arange(S)``."""
    return _ca.causal_attention(q, k, v, interpret=_interpret())


@jax.jit
def rglru_scan(x_gated, log_a, h0=None):
    """[B,S,R] fused RG-LRU -> (h [B,S,R], h_last [B,R])."""
    return _rglru.rglru_scan(x_gated, log_a, h0, interpret=_interpret())


@jax.jit
def wkv6(r, k, v, w, u, s0=None):
    """[B,S,H,dh] chunked WKV6 -> (out, final_state [B,H,dh,dh])."""
    return _rwkv6.wkv6(r, k, v, w, u, s0, interpret=_interpret())


@jax.jit
def moe_gmm(h, w):
    """Grouped matmul h [E,C,D] @ w [E,D,F] -> [E,C,F]."""
    return _gmm.moe_gmm(h, w, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("wire_dtype",))
def wire_encode(x, *, wire_dtype: str = "int8"):
    """Fused wire-codec encode: [..., d] -> (payload, fp32 scales).
    Bit-identical to parallel.wire's jnp reference path (tested)."""
    return _wc.encode_fused(x, wire_dtype, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def wire_decode(q, scale, *, out_dtype="bfloat16"):
    """Fused wire-codec decode: (payload, scales) -> [..., d]."""
    return _wc.decode_fused(q, scale, out_dtype, interpret=_interpret())
