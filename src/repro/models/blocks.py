"""Per-layer blocks: init/apply (full sequence) and decode (single token).

Block kinds:
  * "attn"  — global causal attention + MLP/MoE
  * "local" — sliding-window attention + MLP/MoE (recurrentgemma)
  * "xattn" — self-attn + cross-attn + MLP (whisper decoder)
  * "enc"   — bidirectional self-attn + MLP (whisper encoder)
  * "rglru" — Griffin recurrent block + MLP
  * "rwkv"  — RWKV6 time-mix + channel-mix
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import (activation, apply_norm, chunked_attention,
                                 decode_attention, dense_init, init_norm,
                                 apply_rope)
from repro.models.config import LMConfig
from repro.models.moe import apply_moe, init_moe
from repro.models.recurrent import (apply_recurrent, apply_recurrent_decode,
                                    init_recurrent, init_recurrent_state)
from repro.models.rwkv import (apply_rwkv_channel, apply_rwkv_time,
                               init_rwkv_channel, init_rwkv_time)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _init_attn_params(key, cfg: LMConfig, cross: bool = False):
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "q": dense_init(ks[0], d, cfg.n_heads * hd),
        "k": dense_init(ks[1], d, cfg.n_kv * hd),
        "v": dense_init(ks[2], d, cfg.n_kv * hd),
        "o": dense_init(ks[3], cfg.n_heads * hd, d),
    }
    if cfg.qkv_bias or cfg.mlp_bias:
        p["qb"] = jnp.zeros((cfg.n_heads * hd,))
        p["kb"] = jnp.zeros((cfg.n_kv * hd,))
        p["vb"] = jnp.zeros((cfg.n_kv * hd,))
    if cfg.mlp_bias:
        p["ob"] = jnp.zeros((d,))
    return p


def _init_mlp(key, cfg: LMConfig):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w1": dense_init(ks[0], d, f), "w2": dense_init(ks[1], f, d)}
    if cfg.mlp_gated:
        p["w3"] = dense_init(ks[2], d, f)
    if cfg.mlp_bias:
        p["b1"] = jnp.zeros((f,))
        p["b2"] = jnp.zeros((d,))
    return p


def init_block(key, cfg: LMConfig, kind: str):
    ks = jax.random.split(key, 6)
    if kind == "rwkv":
        return {
            "ln1": init_norm(cfg.d_model, cfg.norm),
            "time": init_rwkv_time(ks[0], cfg.d_model, cfg.rwkv_head_dim,
                                   cfg.rwkv_lora),
            "ln2": init_norm(cfg.d_model, cfg.norm),
            "channel": init_rwkv_channel(ks[1], cfg.d_model, cfg.d_ff),
        }
    p = {"ln1": init_norm(cfg.d_model, cfg.norm),
         "ln2": init_norm(cfg.d_model, cfg.norm)}
    if kind == "rglru":
        p["rec"] = init_recurrent(ks[0], cfg.d_model, cfg.r_width,
                                  cfg.conv_width)
    else:
        p["attn"] = _init_attn_params(ks[0], cfg)
    if kind == "xattn":
        p["lnx"] = init_norm(cfg.d_model, cfg.norm)
        p["cross"] = _init_attn_params(ks[1], cfg, cross=True)
    if cfg.is_moe and kind in ("attn", "local"):
        p["moe"] = init_moe(ks[2], cfg.d_model, cfg.d_ff, cfg.moe_experts)
    else:
        p["mlp"] = _init_mlp(ks[2], cfg)
    return p


# --------------------------------------------------------------------------
# apply (full sequence)
# --------------------------------------------------------------------------

def _proj_qkv(p, h, cfg: LMConfig, dt):
    b, s, _ = h.shape
    hd = cfg.hd
    q = h @ p["q"].astype(dt)
    k = h @ p["k"].astype(dt)
    v = h @ p["v"].astype(dt)
    if "qb" in p:
        q = q + p["qb"].astype(dt)
        k = k + p["kb"].astype(dt)
        v = v + p["vb"].astype(dt)
    return (q.reshape(b, s, cfg.n_heads, hd),
            k.reshape(b, s, cfg.n_kv, hd),
            v.reshape(b, s, cfg.n_kv, hd))


def _mlp(p, h, cfg: LMConfig, dt):
    a = h @ p["w1"].astype(dt)
    if "b1" in p:
        a = a + p["b1"].astype(dt)
    a = activation(a, cfg.act)
    if cfg.mlp_gated:
        a = a * (h @ p["w3"].astype(dt))
    out = a @ p["w2"].astype(dt)
    if "b2" in p:
        out = out + p["b2"].astype(dt)
    return out


def _ffn(p, x, cfg: LMConfig, dt):
    """Second half-block: norm + (MoE | MLP) with residual.  -> (x, aux).
    Its operations carry the ``mlp`` scope in their HLO metadata."""
    with jax.named_scope("mlp"):
        h = apply_norm(x, p["ln2"], cfg.norm)
        if "moe" in p:
            out, aux = apply_moe(p["moe"], h, topk=cfg.moe_topk,
                                 cap_factor=cfg.moe_capacity, act=cfg.act,
                                 global_aux=cfg.moe_global_aux)
            return x + out, aux
        return x + _mlp(p["mlp"], h, cfg, dt), jnp.float32(0.0)


def _ffn_decode(p, x, cfg: LMConfig, dt, per_lane: bool):
    """The decode step's second half-block -> x.  With ``per_lane`` (a
    [B] position: each lane is its own request) a MoE dispatches every
    lane alone, as a batch of one, so a lane's expert capacity, and
    whether its token is dropped, never depends on the other lanes."""
    if per_lane and "moe" in p:
        return jax.vmap(lambda xl: _ffn(p, xl[None], cfg, dt)[0][0])(x)
    return _ffn(p, x, cfg, dt)[0]


def apply_block(p, x, cfg: LMConfig, kind: str, *, positions,
                prefix_len: int = 0, enc_out=None, use_rope: bool = True):
    """x: [B, S, D] -> ([B, S, D], aux_loss).

    ``positions`` must be ``arange(S)``: causal self-attention takes the
    blocked kernel where ``chunked_attention`` allows it, and the kernel
    masks by index, not by these positions."""
    dt = x.dtype
    if kind == "rwkv":
        h = apply_norm(x, p["ln1"], cfg.norm)
        t_out, _ = apply_rwkv_time(p["time"], h, cfg.rwkv_head_dim, dt=dt)
        x = x + t_out
        h = apply_norm(x, p["ln2"], cfg.norm)
        c_out, _ = apply_rwkv_channel(p["channel"], h, dt=dt)
        return x + c_out, jnp.float32(0.0)

    if kind == "rglru":
        h = apply_norm(x, p["ln1"], cfg.norm)
        x = x + apply_recurrent(p["rec"], h, dt=dt)
        return _ffn(p, x, cfg, dt)

    # attention kinds: everything up to the MLP carries the scope
    with jax.named_scope("attention"):
        h = apply_norm(x, p["ln1"], cfg.norm)
        q, k, v = _proj_qkv(p["attn"], h, cfg, dt)
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        causal = kind != "enc"
        window = cfg.window if kind == "local" else 0
        att = chunked_attention(q, k, v, q_positions=positions,
                                kv_positions=positions, causal=causal,
                                window=window, prefix_len=prefix_len)
        b, s, _, _ = att.shape
        att = att.reshape(b, s, cfg.n_heads * cfg.hd) @ p["attn"]["o"].astype(dt)
        if "ob" in p["attn"]:
            att = att + p["attn"]["ob"].astype(dt)
        x = x + att

        if kind == "xattn":
            assert enc_out is not None
            h = apply_norm(x, p["lnx"], cfg.norm)
            bq, sq, _ = h.shape
            se = enc_out.shape[1]
            hd = cfg.hd
            q = (h @ p["cross"]["q"].astype(dt)).reshape(bq, sq, cfg.n_heads, hd)
            ck = (enc_out @ p["cross"]["k"].astype(dt)).reshape(bq, se, cfg.n_kv, hd)
            cv = (enc_out @ p["cross"]["v"].astype(dt)).reshape(bq, se, cfg.n_kv, hd)
            att = chunked_attention(q, ck, cv,
                                    q_positions=jnp.arange(sq),
                                    kv_positions=jnp.arange(se), causal=False)
            x = x + att.reshape(bq, sq, cfg.n_heads * hd) @ p["cross"]["o"].astype(dt)

    return _ffn(p, x, cfg, dt)


# --------------------------------------------------------------------------
# prefill: full-sequence forward that also emits the decode state
# --------------------------------------------------------------------------

def _kv_into_cache(k, v, cache_len: int, window: int = 0):
    """Pack full-sequence K/V [B, S, kv, hd] into the decode cache layout.

    Global attention: zero-padded [B, cache_len, kv, hd].
    Local attention: the ring buffer holding the last ``window`` tokens at
    slots t % window (matching apply_block_decode's ring indexing).
    """
    b, s, n_kv, hd = k.shape
    if window > 0:
        w = min(window, cache_len)
        take = min(w, s)
        ts = jnp.arange(s - take, s)
        slots = ts % w
        kc = jnp.zeros((b, w, n_kv, hd), k.dtype).at[:, slots].set(
            k[:, s - take:])
        vc = jnp.zeros((b, w, n_kv, hd), v.dtype).at[:, slots].set(
            v[:, s - take:])
        return {"k": kc, "v": vc}
    pad = cache_len - s
    assert pad >= 0, (s, cache_len)
    zp = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return {"k": zp(k), "v": zp(v)}


def apply_block_prefill(p, x, cfg: LMConfig, kind: str, *, positions,
                        cache_len: int, prefix_len: int = 0, enc_out=None,
                        use_rope: bool = True):
    """Full-sequence forward returning (y, aux, decode_state).

    ``positions`` must be ``arange(S)``, as in ``apply_block``."""
    dt = x.dtype
    if kind == "rwkv":
        h = apply_norm(x, p["ln1"], cfg.norm)
        t_out, (lx, s_fin) = apply_rwkv_time(p["time"], h, cfg.rwkv_head_dim,
                                             dt=dt)
        x = x + t_out
        h2 = apply_norm(x, p["ln2"], cfg.norm)
        c_out, lc = apply_rwkv_channel(p["channel"], h2, dt=dt)
        y = x + c_out
        return y, jnp.float32(0.0), {"s": s_fin, "shift_t": lx,
                                     "shift_c": lc}

    if kind == "rglru":
        h = apply_norm(x, p["ln1"], cfg.norm)
        out, st = apply_recurrent(p["rec"], h, dt=dt, return_state=True)
        x = x + out
        y, aux = _ffn(p, x, cfg, dt)
        return y, aux, st

    # attention kinds
    with jax.named_scope("attention"):
        h = apply_norm(x, p["ln1"], cfg.norm)
        q, k, v = _proj_qkv(p["attn"], h, cfg, dt)
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        window = cfg.window if kind == "local" else 0
        att = chunked_attention(q, k, v, q_positions=positions,
                                kv_positions=positions, causal=(kind != "enc"),
                                window=window, prefix_len=prefix_len)
        state = _kv_into_cache(k, v, cache_len, window=window)
        b, s, _, _ = att.shape
        att = att.reshape(b, s, cfg.n_heads * cfg.hd) @ p["attn"]["o"].astype(dt)
        if "ob" in p["attn"]:
            att = att + p["attn"]["ob"].astype(dt)
        x = x + att

        if kind == "xattn":
            assert enc_out is not None
            h = apply_norm(x, p["lnx"], cfg.norm)
            bq, sq, _ = h.shape
            se = enc_out.shape[1]
            hd = cfg.hd
            q = (h @ p["cross"]["q"].astype(dt)).reshape(bq, sq, cfg.n_heads, hd)
            ck = (enc_out @ p["cross"]["k"].astype(dt)).reshape(bq, se,
                                                                cfg.n_kv, hd)
            cv = (enc_out @ p["cross"]["v"].astype(dt)).reshape(bq, se,
                                                                cfg.n_kv, hd)
            att = chunked_attention(q, ck, cv, q_positions=jnp.arange(sq),
                                    kv_positions=jnp.arange(se), causal=False)
            x = x + att.reshape(bq, sq, cfg.n_heads * hd) \
                @ p["cross"]["o"].astype(dt)
            state["ck"] = ck
            state["cv"] = cv

    y, aux = _ffn(p, x, cfg, dt)
    return y, aux, state


# --------------------------------------------------------------------------
# decode (single token with state)
# --------------------------------------------------------------------------

def init_block_state(cfg: LMConfig, kind: str, batch: int, cache_len: int,
                     dtype=jnp.bfloat16):
    hd = cfg.hd
    if kind == "xattn":
        # cross-attention K/V are computed ONCE from the encoder memory at
        # prefill (LM.fill_cross_kv) — recomputing the 1500-frame
        # projections per decoded token dominated decode FLOPs
        # (EXPERIMENTS.md §Perf, whisper decode useful-flops 0.010).
        return {"k": jnp.zeros((batch, cache_len, cfg.n_kv, hd), dtype),
                "v": jnp.zeros((batch, cache_len, cfg.n_kv, hd), dtype),
                "ck": jnp.zeros((batch, cfg.enc_seq, cfg.n_kv, hd), dtype),
                "cv": jnp.zeros((batch, cfg.enc_seq, cfg.n_kv, hd), dtype)}
    if kind in ("attn", "enc"):
        return {"k": jnp.zeros((batch, cache_len, cfg.n_kv, hd), dtype),
                "v": jnp.zeros((batch, cache_len, cfg.n_kv, hd), dtype)}
    if kind == "local":
        w = min(cfg.window, cache_len) or cache_len
        return {"k": jnp.zeros((batch, w, cfg.n_kv, hd), dtype),
                "v": jnp.zeros((batch, w, cfg.n_kv, hd), dtype)}
    if kind == "rglru":
        return init_recurrent_state(batch, cfg.r_width, cfg.conv_width)
    if kind == "rwkv":
        h = cfg.d_model // cfg.rwkv_head_dim
        return {"s": jnp.zeros((batch, h, cfg.rwkv_head_dim,
                                cfg.rwkv_head_dim), jnp.float32),
                "shift_t": jnp.zeros((batch, cfg.d_model), dtype),
                "shift_c": jnp.zeros((batch, cfg.d_model), dtype)}
    raise ValueError(kind)


def _row(a, layer):
    """This block's rows of a cache leaf: the leaf itself, or row
    ``layer`` of a scan-stacked [L, ...] leaf."""
    return a if layer is None else jax.lax.dynamic_index_in_dim(
        a, layer, 0, keepdims=False)


def _write_token(buf, new, idx, layer):
    """Write one token per lane, ``new`` [B, 1, ...], at index ``idx`` of
    ``buf`` [B, S, ...] (or of its row ``layer`` when ``buf`` is
    [L, B, S, ...]), and nothing else of ``buf``, so a donated cache is
    updated in place.  A scalar ``idx`` (every lane at one position) is
    one dynamic-update-slice; a [B] ``idx`` is one per lane.

    Every lane writes, an inactive one too, at the index of its held
    position.  That is safe because nothing reads a lane's cache while
    it is inactive, and the lane's next active step, at that same held
    position, writes the same index before its attention reads it.
    Holding the token instead (a select against the token there, a
    scatter that drops the lane) or writing all lanes with one scatter
    makes the TPU compiler copy the whole cache into another layout and
    back around the step."""
    new = new.astype(buf.dtype)
    lead = () if layer is None else (layer,)
    tail = (0,) * (new.ndim - 2)
    if idx.ndim == 0:
        upd = new.reshape((1,) * len(lead) + new.shape)
        return jax.lax.dynamic_update_slice(buf, upd, lead + (0, idx) + tail)
    for b in range(new.shape[0]):
        upd = new[b:b + 1].reshape((1,) * len(lead) + (1,) + new.shape[1:])
        buf = jax.lax.dynamic_update_slice(buf, upd, lead + (b, idx[b]) + tail)
    return buf


def _hold_state(state, old, new, active, layer):
    """A recurrent block's new state, ``old`` (its rows of ``state``)
    held where ``active`` is False, put back into row ``layer`` of a
    scan-stacked state."""
    if active is not None:
        new = jax.tree.map(
            lambda n, o: jnp.where(
                active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
            new, old)
    if layer is None:
        return new
    return jax.tree.map(lambda a, n: a.at[layer].set(n.astype(a.dtype)),
                        state, new)


def apply_block_decode(p, x, state, cfg: LMConfig, kind: str, *, position,
                       enc_out=None, use_rope: bool = True, active=None,
                       layer=None):
    """x: [B, 1, D], state per kind -> ([B, 1, D], new_state).

    ``position`` is a scalar or a [B] vector, one position per lane.
    A [B] vector also makes each lane's MoE dispatch its own
    (``_ffn_decode``).  ``active`` ([B] bool, optional): lanes where it
    is False keep their recurrent state; every lane writes its K/V token,
    under the condition ``_write_token`` states.  With ``layer`` (a
    traced index) ``state`` is the whole scan-stacked cache ([L, B, ...]
    leaves) and the block reads and writes its row ``layer`` of it.  The
    state's write carries the ``arena`` scope, the name the serving
    engine's slot arena is measured by.
    """
    dt = x.dtype
    b = x.shape[0]
    if kind == "rwkv":
        st = jax.tree.map(lambda a: _row(a, layer), state)
        h = apply_norm(x, p["ln1"], cfg.norm)
        t_out, (lx, s_new) = apply_rwkv_time(
            p["time"], h, cfg.rwkv_head_dim,
            shift_in=st["shift_t"], state_in=st["s"], dt=dt)
        x = x + t_out
        h = apply_norm(x, p["ln2"], cfg.norm)
        c_out, lc = apply_rwkv_channel(p["channel"], h,
                                       shift_in=st["shift_c"], dt=dt)
        with jax.named_scope("arena"):
            new_state = _hold_state(
                state, st, {"s": s_new, "shift_t": lx, "shift_c": lc},
                active, layer)
        return x + c_out, new_state

    if kind == "rglru":
        st = jax.tree.map(lambda a: _row(a, layer), state)
        h = apply_norm(x, p["ln1"], cfg.norm)
        out, s_new = apply_recurrent_decode(p["rec"], h, st, dt=dt)
        x = x + out
        x = _ffn_decode(p, x, cfg, dt, jnp.ndim(position) == 1)
        with jax.named_scope("arena"):
            new_state = _hold_state(state, st, s_new, active, layer)
        return x, new_state

    pos = jnp.broadcast_to(jnp.asarray(position, jnp.int32), (b,))
    with jax.named_scope("attention"):
        h = apply_norm(x, p["ln1"], cfg.norm)
        q, k, v = _proj_qkv(p["attn"], h, cfg, dt)
        if use_rope:
            q = apply_rope(q, pos[:, None], cfg.rope_theta)
            k = apply_rope(k, pos[:, None], cfg.rope_theta)
        w = state["k"].shape[-3]
        idx = jnp.asarray(position, jnp.int32)    # scalar, or one per lane
        if kind == "local":
            idx = idx % w
        with jax.named_scope("arena"):
            new_state = {"k": _write_token(state["k"], k, idx, layer),
                         "v": _write_token(state["v"], v, idx, layer)}
        k_cache = _row(new_state["k"], layer)
        v_cache = _row(new_state["v"], layer)
        if kind == "local":
            # ring buffer: all entries valid once warm; mask handled by window
            att = decode_attention(q, k_cache, v_cache,
                                   position=jnp.minimum(pos, w - 1),
                                   window=0)
        else:
            att = decode_attention(q, k_cache, v_cache, position=pos)
        att = att.reshape(b, 1, cfg.n_heads * cfg.hd) @ p["attn"]["o"].astype(dt)
        if "ob" in p["attn"]:
            att = att + p["attn"]["ob"].astype(dt)
        x = x + att

        if kind == "xattn":
            h = apply_norm(x, p["lnx"], cfg.norm)
            hd = cfg.hd
            q = (h @ p["cross"]["q"].astype(dt)).reshape(b, 1, cfg.n_heads, hd)
            if "ck" in state:          # precomputed at prefill
                ck = _row(state["ck"], layer).astype(dt)
                cv = _row(state["cv"], layer).astype(dt)
                new_state["ck"] = state["ck"]
                new_state["cv"] = state["cv"]
            else:
                assert enc_out is not None
                se = enc_out.shape[1]
                ck = (enc_out @ p["cross"]["k"].astype(dt)).reshape(
                    b, se, cfg.n_kv, hd)
                cv = (enc_out @ p["cross"]["v"].astype(dt)).reshape(
                    b, se, cfg.n_kv, hd)
            att = decode_attention(q, ck, cv, position=ck.shape[1] - 1)
            x = x + att.reshape(b, 1, cfg.n_heads * hd) @ p["cross"]["o"].astype(dt)

    x = _ffn_decode(p, x, cfg, dt, jnp.ndim(position) == 1)
    return x, new_state
