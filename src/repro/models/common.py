"""Shared model building blocks (pure JAX, functional, pytree params)."""
from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp

from repro.kernels import causal_attention as _ca


def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32):
    scale = 1.0 / math.sqrt(d_in)
    return jax.random.normal(key, (d_in, d_out), dtype) * scale


def match_vma(val, ref):
    """Give ``val`` (a freshly-created scan carry) the same varying-manual-
    axes as ``ref`` — required when model code runs inside a partial-manual
    shard_map (the C2P2SL pod pipeline), where zero-initialized carries are
    otherwise 'unvarying' and scan rejects the carry type mismatch."""
    from repro.parallel.compat import match_vma as _match_vma
    return _match_vma(val, ref)


def rmsnorm(x, w, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, w, b, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def apply_norm(x, p, kind: str):
    if kind == "rmsnorm":
        return rmsnorm(x, p["w"])
    return layernorm(x, p["w"], p["b"])


def init_norm(d: int, kind: str, dtype=jnp.float32):
    if kind == "rmsnorm":
        return {"w": jnp.zeros((d,), dtype)}
    return {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def activation(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu":
        return jax.nn.relu(x)
    if kind == "sqrelu":
        return jnp.square(jax.nn.relu(x))
    raise ValueError(kind)


# --- rotary embeddings ------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: [..., S, H, dh]; positions: [..., S] (broadcastable)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # [dh/2]
    ang = positions[..., None].astype(jnp.float32) * freqs   # [..., S, dh/2]
    cos = jnp.cos(ang)[..., None, :]                    # [..., S, 1, dh/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --- attention (chunked, flash-style online softmax over query blocks) ------

NEG_INF = -1e30


@jax.checkpoint
def _attend_block(q, k, v, mask):
    """q [B,hq,G,dh] (G=q block), k/v [B,hkv,S,dh], mask [G,S] or
    [B,G,S] bool.

    ``jax.checkpoint`` = flash-attention-style backward: the [G,S] logits /
    probabilities are recomputed in the backward pass instead of being saved
    per query chunk (which would reconstitute the full [Sq,Skv] matrix).
    """
    b, hq, g, dh = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    qg = q.reshape(b, hkv, rep, g, dh)
    logits = jnp.einsum("bkrgd,bksd->bkrgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(dh)
    mask = mask[None, None, None] if mask.ndim == 2 else mask[:, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkrgs,bksd->bkrgd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, g, dh).astype(q.dtype)


class AttentionPaths(collections.Counter):
    """The path of each attention call traced while this counter is
    entered (``with paths:``): ``"kernel"``, or ``"xla: <reason>"`` for
    the jnp path and why the kernel did not take the call.  A traced
    call is one per program and shape: a scanned layer stack traces its
    layers' attention once.  Counters nest; each entered one counts."""

    def __enter__(self):
        _RECORDING.append(self)
        return self

    def __exit__(self, *exc):
        # by identity: a Counter equals any other of the same counts
        del _RECORDING[max(i for i, c in enumerate(_RECORDING)
                           if c is self)]


_RECORDING: list = []


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention_path(q, k, *, self_attention: bool, causal: bool,
                   window: int = 0, prefix_len: int = 0) -> str:
    """Which path an attention call takes: ``"kernel"`` (the blocked
    Pallas kernel, ``kernels/causal_attention.py``) or ``"xla: <reason>"``.

    The kernel takes causal self-attention over one sequence, with no
    window and no prefix, S and dh multiples of 128, on a TPU backend.
    """
    s, dh = q.shape[1], q.shape[-1]
    if not _on_tpu():
        why = f"backend {jax.default_backend()}"
    elif not self_attention or k.shape[1] != s:
        why = "cross"
    elif not causal:
        why = "not causal"
    elif window > 0:
        why = "window"
    elif prefix_len > 0:
        why = "prefix"
    elif s % _ca.BLOCK:
        why = f"seq {s} % {_ca.BLOCK}"
    elif dh % _ca.BLOCK:
        why = f"head_dim {dh} % {_ca.BLOCK}"
    else:
        return "kernel"
    return f"xla: {why}"


def chunked_attention(q, k, v, *, q_positions, kv_positions, causal: bool,
                      window: int = 0, prefix_len: int = 0,
                      q_chunk: int = 512):
    """Memory-efficient attention.

    q: [B, Sq, Hq, dh]; k, v: [B, Skv, Hkv, dh].
    ``window`` > 0 restricts to a sliding causal window (local attention).
    ``prefix_len`` > 0 makes positions < prefix_len bidirectional (VLM
    prefix-LM masking).

    Self-attention passes one positions array as both ``q_positions``
    and ``kv_positions``, and it must be ``arange(S)``: where
    ``attention_path`` takes the kernel, it masks by index and never
    reads the positions.  Every other call (and each off the TPU) takes
    the jnp path: a scan over query chunks that
    never materializes [B, H, Sq, Skv]; its peak scratch is
    [B, H, q_chunk, Skv].  Each traced call is counted in the entered
    ``AttentionPaths``.
    """
    path = attention_path(q, k, self_attention=kv_positions is q_positions,
                          causal=causal, window=window,
                          prefix_len=prefix_len)
    for paths in _RECORDING:
        paths[path] += 1
    if path == "kernel":
        from repro.kernels import ops
        return ops.causal_attention(q, k, v)

    b, sq, hq, dh = q.shape
    skv = k.shape[1]
    qt = jnp.swapaxes(q, 1, 2)          # [B,Hq,Sq,dh]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    def mask_for(qpos):
        # qpos [G], kv_positions [Skv]
        qp = qpos[:, None]
        kp = kv_positions[None, :]
        m = jnp.ones((qpos.shape[0], skv), dtype=bool)
        if causal:
            cm = kp <= qp
            if prefix_len > 0:
                cm = cm | (kp < prefix_len)
            m = m & cm
        if window > 0:
            m = m & (kp > qp - window)
        return m

    if sq <= q_chunk:
        out = _attend_block(qt, kt, vt, mask_for(q_positions))
        return jnp.swapaxes(out, 1, 2)

    n_chunks = -(-sq // q_chunk)
    pad = n_chunks * q_chunk - sq
    qp = jnp.pad(qt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    pos = jnp.pad(q_positions, (0, pad), constant_values=-1)
    qs = qp.reshape(b, hq, n_chunks, q_chunk, dh)
    poss = pos.reshape(n_chunks, q_chunk)

    def body(_, inp):
        qc, pc = inp
        return None, _attend_block(qc, kt, vt, mask_for(pc))

    _, outs = jax.lax.scan(body, None,
                           (jnp.moveaxis(qs, 2, 0), poss))
    out = jnp.moveaxis(outs, 0, 2).reshape(b, hq, n_chunks * q_chunk, dh)
    return jnp.swapaxes(out[:, :, :sq], 1, 2)


def decode_attention(q, k_cache, v_cache, *, position, window: int = 0):
    """Single-token decode: q [B,1,Hq,dh], caches [B,S,Hkv,dh].

    ``position`` (a scalar, or [B]: one per lane) is the index of the
    token being generated; cache entries at kv index > position (or
    outside the local window) are masked.
    """
    b = q.shape[0]
    s = k_cache.shape[1]
    kv_pos = jnp.arange(s)[None, :]
    pos = jnp.broadcast_to(jnp.asarray(position), (b,))[:, None]
    mask = kv_pos <= pos
    if window > 0:
        mask = mask & (kv_pos > pos - window)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k_cache, 1, 2)
    vt = jnp.swapaxes(v_cache, 1, 2)
    out = _attend_block(qt, kt, vt, mask[:, None, :])
    return jnp.swapaxes(out, 1, 2)
