"""Fused Pallas wire-codec kernels for the pipeline hop payload.

PR 5's wire codec (``parallel/wire.py``) block-quantizes the cut
activation with separate jnp ops — absmax reduce, scale clamp, divide,
round/clip, cast — each a round trip through HBM.  These kernels fuse the
whole encode (and decode) into one ``pallas_call`` per direction: a row
tile of the activation is loaded into VMEM once, per-block scales are
computed and the quantized payload + fp32 scales are written out, at
~memory-bandwidth cost (the bench: benchmarks/wire_codec.py, which also
feeds the measured ``codec_s_per_byte`` planner hint).

Layout contract (identical to the jnp reference path):

    x [..., d]  ->  payload [..., d/b, b] int8|fp8-e4m3, scales [..., d/b, 1]

with ``b = wire_block(d)`` — the largest divisor of d_model <= 256, so
the wire never carries padding bytes.  The kernel body mirrors
``training.compress.quantize_blocks`` op for op (astype f32 -> blocked
absmax -> ``max(amax/qmax, 1e-12)`` -> divide -> round/clip/cast), so
interpret mode is BIT-IDENTICAL to the jnp path — the parity contract
tests/test_wire_codec.py locks.  On a TPU backend the same body compiles
to Mosaic; off-TPU callers (``kernels/ops.py``) run ``interpret=True``.

``wire_block`` lives here (the kernel layer owns its blocking);
``parallel/wire.py`` re-exports it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.training.compress import payload_dtype, qmax_for


# On-wire HLO element type of the quantized payload each base codec puts
# on the hop (what the ppermute'd buffer must spell in compiled HLO).
# fp8 payloads are ``s8`` too: ``wire._wire_ppermute`` bitcasts 1-byte
# float payloads to int8 around the collective so no backend
# legalization can re-inflate the wire (XLA:CPU upcasts f8 collectives
# to f16).  The kernel layer owns the payload format, so the canonical
# mapping lives here; ``repro.analysis.staticcheck`` mirrors it
# numpy-only (this module imports jax/pallas) and a tier-1 test pins the
# two copies together — change one without the other and the auditor's
# contract test fails.
PAYLOAD_HLO_DTYPE = {"int8": "s8", "fp8": "s8"}


def wire_block(dim: int, block: int = 256) -> int:
    """Largest block size <= ``block`` dividing ``dim`` (no padding)."""
    b = min(block, max(dim, 1))
    while dim % b:
        b -= 1
    return b


def _row_tile(rows: int, cap: int = 128) -> int:
    """Per-grid-step row count: all ``rows`` when they fit in ``cap``,
    else the largest multiple of 8 <= ``cap`` dividing ``rows``, else
    ``cap`` with a partial last block (``pl.cdiv`` grid).

    Mosaic tiles the second-to-last block dim by 8, so a block must be a
    multiple of 8 rows or the whole array.  Rows are independent, so the
    padded rows of a partial last block never reach a real output."""
    if rows <= cap:
        return max(rows, 1)
    for t in range(cap - cap % 8, 7, -8):
        if rows % t == 0:
            return t
    return cap - cap % 8


def _encode_kernel(x_ref, q_ref, s_ref, *, nb: int, b: int, wire_dtype: str):
    # Mirror of training.compress.quantize_blocks, op for op, on one
    # [rt, d] row tile resident in VMEM.
    x = x_ref[...].astype(jnp.float32)
    blocks = x.reshape(x.shape[0], nb, b)
    amax = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / qmax_for(wire_dtype), 1e-12)
    scaled = blocks / scale
    if wire_dtype == "int8":
        q = jnp.clip(jnp.round(scaled), -127, 127).astype(jnp.int8)
    else:
        q = scaled.astype(payload_dtype("fp8"))
    q_ref[...] = q
    s_ref[...] = scale


def _decode_kernel(q_ref, s_ref, o_ref, *, out_dtype):
    x = q_ref[...].astype(jnp.float32) * s_ref[...]
    o_ref[...] = x.reshape(x.shape[0], -1).astype(out_dtype)


def encode_fused(x, wire_dtype: str, *, interpret: bool = False):
    """[..., d] -> (payload [..., d/b, b], fp32 scales [..., d/b, 1]) in
    one fused pass; bit-identical to the jnp reference in interpret mode."""
    d = x.shape[-1]
    b = wire_block(d)
    nb = d // b
    lead = x.shape[:-1]
    rows = max(1, math.prod(lead))
    x2 = x.reshape(rows, d)
    rt = _row_tile(rows)
    qdt = payload_dtype(wire_dtype)
    # inside a checked shard_map (the pipeline hop) the outputs vary over
    # the same manual axes as the input
    vma = jax.typeof(x).vma
    q, s = pl.pallas_call(
        functools.partial(_encode_kernel, nb=nb, b=b, wire_dtype=wire_dtype),
        grid=(pl.cdiv(rows, rt),),
        in_specs=[pl.BlockSpec((rt, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rt, nb, b), lambda i: (i, 0, 0)),
            pl.BlockSpec((rt, nb, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, nb, b), qdt, vma=vma),
            jax.ShapeDtypeStruct((rows, nb, 1), jnp.float32, vma=vma),
        ],
        interpret=interpret,
        name="wire_encode",
    )(x2)
    return q.reshape(lead + (nb, b)), s.reshape(lead + (nb, 1))


def decode_fused(q, scale, out_dtype, *, interpret: bool = False):
    """(payload [..., d/b, b], scales [..., d/b, 1]) -> [..., d] at
    ``out_dtype``; the fused inverse of ``encode_fused``."""
    nb, b = q.shape[-2], q.shape[-1]
    lead = q.shape[:-2]
    rows = max(1, math.prod(lead))
    odt = jnp.dtype(out_dtype)
    q2 = q.reshape(rows, nb, b)
    s2 = scale.reshape(rows, nb, 1)
    rt = _row_tile(rows)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, out_dtype=odt),
        grid=(pl.cdiv(rows, rt),),
        in_specs=[
            pl.BlockSpec((rt, nb, b), lambda i: (i, 0, 0)),
            pl.BlockSpec((rt, nb, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((rt, nb * b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, nb * b), odt,
                                       vma=jax.typeof(q).vma),
        interpret=interpret,
        name="wire_decode",
    )(q2, s2)
    return out.reshape(lead + (nb * b,))
