"""Top-level language-model assembly: init / train forward / decode.

Handles every zoo family through ``LMConfig``:
  * dense / GQA / MoE decoders (scan-over-layers, rematerialized)
  * hybrid patterns (recurrentgemma: rglru+local attn, unrolled loop)
  * rwkv6 (attention-free)
  * whisper (enc-dec with cross attention, stub conv frontend)
  * paligemma (stub patch embeddings, prefix-LM masking)

The vocabulary cross-entropy is sequence-chunked and rematerialized so the
[B, S, V] logits tensor is never alive at once — required for 256k vocabs
at 4k sequence length.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.blocks import (apply_block, apply_block_decode,
                                 apply_block_prefill, init_block,
                                 init_block_state)
from repro.models.common import apply_norm, init_norm
from repro.models.config import LMConfig
from repro.parallel.context import constrain, get_ctx


def _sin_pos(seq: int, d: int, offset=0):
    pos = jnp.arange(seq) + offset
    half = d // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = pos[:, None] * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _softcap(logits, cap: float):
    if cap and cap > 0:
        return cap * jnp.tanh(logits / cap)
    return logits


def _find_period(kinds) -> tuple:
    """Smallest repeating prefix period covering >= 2 groups of layers."""
    n = len(kinds)
    for p in range(1, n // 2 + 1):
        g = n // p
        if all(kinds[i] == kinds[i % p] for i in range(g * p)):
            return p, g
    return n, 1


# params consumed in f32 inside the blocks (norms, gates, routers, decay
# LoRAs) — everything else is matmul weight, safe to pre-cast
_KEEP_F32 = {"ln1", "ln2", "lnx", "enc_norm", "final_norm", "gate_a",
             "gate_x", "lambda", "router", "w0", "w_a", "w_b", "u",
             "ln_w", "ln_b"}


def cast_gather_weights(tree, dt):
    """Pre-cast matmul weights to the compute dtype.

    The cast is elementwise, so it runs on the SHARDED resident weights;
    the per-layer FSDP all-gather then moves bf16 instead of f32 — half
    the collective bytes and half the gathered-weight HBM traffic.
    """
    def one(path, x):
        if x.dtype != jnp.float32 or x.ndim < 2:
            return x
        for p in path:
            if hasattr(p, "key") and str(p.key) in _KEEP_F32:
                return x
        return x.astype(dt)
    return jax.tree_util.tree_map_with_path(one, tree)


class LM:
    """Functional model wrapper; all methods are pure."""

    def __init__(self, cfg: LMConfig):
        self.cfg = cfg

    # ---------------- init ----------------

    def init(self, key):
        cfg = self.cfg
        kinds = cfg.layer_kinds
        k_embed, k_blocks, k_head, k_enc = jax.random.split(key, 4)
        params = {
            "embed": jax.random.normal(
                k_embed, (cfg.padded_vocab, cfg.d_model)) * 0.02,
            "final_norm": init_norm(cfg.d_model, cfg.norm),
        }
        if cfg.homogeneous:
            keys = jax.random.split(k_blocks, cfg.num_layers)
            params["blocks"] = jax.vmap(
                lambda k: init_block(k, cfg, kinds[0]))(keys)
        else:
            keys = jax.random.split(k_blocks, cfg.num_layers)
            params["blocks"] = tuple(
                init_block(keys[i], cfg, kinds[i])
                for i in range(cfg.num_layers))
        if not cfg.tie_embeddings:
            params["head"] = jax.random.normal(
                k_head, (cfg.d_model, cfg.padded_vocab)) * 0.02
        if cfg.enc_layers:
            ekeys = jax.random.split(k_enc, cfg.enc_layers)
            params["enc_blocks"] = jax.vmap(
                lambda k: init_block(k, cfg, "enc"))(ekeys)
            params["enc_norm"] = init_norm(cfg.d_model, cfg.norm)
        return params

    # ---------------- backbone ----------------

    def _embed(self, params, tokens, dt):
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = params["embed"].astype(dt)[tokens]
            if cfg.embed_scale:
                x = x * jnp.asarray(math.sqrt(cfg.d_model), dt)
            return x

    def _run_blocks(self, params, x, *, positions, prefix_len=0, enc_out=None):
        cfg = self.cfg
        kinds = cfg.layer_kinds
        ctx = get_ctx()
        if ctx.cast_gathers:
            params = dict(params)
            params["blocks"] = cast_gather_weights(params["blocks"], x.dtype)
        aux_total = jnp.float32(0.0)

        def one_block(layer_params, h, kind):
            y, aux = apply_block(layer_params, h, cfg, kind,
                                 positions=positions,
                                 prefix_len=prefix_len, enc_out=enc_out,
                                 use_rope=(kind != "rwkv"))
            return constrain(y, ctx.hidden_spec), aux

        if cfg.homogeneous and not isinstance(params["blocks"], tuple):
            kind = kinds[0]

            @jax.checkpoint
            def body(carry, layer_params):
                return one_block(layer_params, carry, kind)

            x, auxes = jax.lax.scan(body, x, params["blocks"])
            aux_total = auxes.sum()
        else:
            # Heterogeneous pattern (recurrentgemma): scan over period-
            # stacked units instead of unrolling — an unrolled layer loop
            # makes XLA's buffer assignment hold every layer's rematted
            # temps concurrently (~5.7 GiB/layer; EXPERIMENTS.md §Perf).
            period, groups = _find_period(kinds)
            blocks = params["blocks"]
            if groups >= 2:
                stacked = tuple(
                    jax.tree.map(lambda *ls: jnp.stack(ls),
                                 *[blocks[g * period + j]
                                   for g in range(groups)])
                    for j in range(period))

                @jax.checkpoint
                def unit(carry, unit_params):
                    aux_u = jnp.float32(0.0)
                    for j in range(period):
                        carry, aux = one_block(unit_params[j], carry,
                                               kinds[j])
                        aux_u = aux_u + aux
                    return carry, aux_u

                x, auxes = jax.lax.scan(unit, x, stacked)
                aux_total = auxes.sum()
                start = groups * period
            else:
                start = 0
            for i in range(start, cfg.num_layers):
                x, aux = jax.checkpoint(
                    lambda p, h, k=kinds[i]: one_block(p, h, k))(blocks[i], x)
                aux_total = aux_total + aux
        return x, aux_total

    def _encode(self, params, frames):
        """Whisper encoder on stub frame embeddings [B, T, D]."""
        cfg = self.cfg
        dt = frames.dtype
        t = frames.shape[1]
        x = frames + _sin_pos(t, cfg.d_model).astype(dt)[None]
        positions = jnp.arange(t)

        def body(carry, layer_params):
            y, _ = apply_block(layer_params, carry, cfg, "enc",
                               positions=positions, use_rope=False)
            return y, None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["enc_blocks"])
        return apply_norm(x, params["enc_norm"], cfg.norm)

    # ---------------- losses ----------------

    def _head_w(self, params, dt):
        if self.cfg.tie_embeddings:
            return params["embed"].astype(dt).T
        return params["head"].astype(dt)

    def head_loss(self, params, h, labels):
        """Final norm, head and cross entropy of the last hidden states
        ``h`` [B, S, D]; its operations carry the ``head_loss`` scope."""
        with jax.named_scope("head_loss"):
            h = apply_norm(h, params["final_norm"], self.cfg.norm)
            return self.xent(params, h, labels)

    def xent(self, params, h, labels, chunk: int = 512):
        """Chunked softmax cross entropy.  h [B,S,D], labels [B,S] (-1 pad)."""
        cfg = self.cfg
        dt = h.dtype
        b, s, d = h.shape
        w = self._head_w(params, dt)
        nc = -(-s // chunk)
        pad = nc * chunk - s
        if pad:
            h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
        hs = jnp.moveaxis(h.reshape(b, nc, chunk, d), 1, 0)
        ls = jnp.moveaxis(labels.reshape(b, nc, chunk), 1, 0)

        @jax.checkpoint
        def body(carry, inp):
            hc, lc = inp
            logits = _softcap(hc @ w, cfg.logit_softcap).astype(jnp.float32)
            if cfg.padded_vocab != cfg.vocab:
                neg = jnp.full((cfg.padded_vocab - cfg.vocab,), -1e30,
                               jnp.float32)
                logits = logits.at[..., cfg.vocab:].set(neg)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            safe = jnp.maximum(lc, 0)
            ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
            mask = (lc >= 0).astype(jnp.float32)
            loss_sum, count = carry
            return (loss_sum + jnp.sum((lse - ll) * mask),
                    count + mask.sum()), None

        (loss_sum, count), _ = jax.lax.scan(
            body, (jnp.float32(0.0), jnp.float32(0.0)), (hs, ls))
        return loss_sum / jnp.maximum(count, 1.0)

    # ---------------- public API ----------------

    def forward(self, params, batch):
        """Training/prefill forward.  Returns (loss, metrics)."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        tokens = batch["tokens"]
        labels = batch.get("labels")
        ctx = get_ctx()
        prefix_len = 0
        enc_out = None

        x = self._embed(params, tokens, dt)
        if cfg.family == "vlm":
            patches = batch["patch_embeds"].astype(dt)
            x = jnp.concatenate([patches, x], axis=1)
            prefix_len = patches.shape[1]
            if labels is not None:
                pad = jnp.full(patches.shape[:2], -1, labels.dtype)
                labels = jnp.concatenate([pad, labels], axis=1)
        if cfg.family == "audio":
            enc_out = self._encode(params, batch["frames"].astype(dt))
        x = constrain(x, ctx.hidden_spec)

        positions = jnp.arange(x.shape[1])
        x, aux = self._run_blocks(params, x, positions=positions,
                                  prefix_len=prefix_len, enc_out=enc_out)
        if labels is None:
            return apply_norm(x, params["final_norm"], cfg.norm), {"aux": aux}
        loss = self.head_loss(params, x, labels)
        total = loss + 0.01 * aux
        return total, {"xent": loss, "aux": aux}

    def hidden(self, params, batch):
        """Final hidden states without loss (serving prefill)."""
        out, _ = self.forward(params, {**batch, "labels": None})
        return out

    def init_cache(self, batch: int, cache_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        kinds = cfg.layer_kinds
        if cfg.homogeneous:
            # stacked state for scan-decode
            one = init_block_state(cfg, kinds[0], batch, cache_len, dtype)
            return jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a[None], (cfg.num_layers,) + a.shape), one)
        return tuple(init_block_state(cfg, k, batch, cache_len, dtype)
                     for k in kinds)

    def prefill_with_cache(self, params, batch, cache_len: int,
                           cache_dtype=jnp.bfloat16):
        """Chunked prefill: ONE full-sequence forward that also fills the
        decode cache (K/V buffers, ring buffers, recurrent states, cross
        K/V) — the production serving path, vs feeding the prompt through
        decode_step token by token.

        Returns (last-position logits [B, V], serve_state).
        """
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        kinds = cfg.layer_kinds
        tokens = batch["tokens"]
        prefix_len = 0
        enc_out = None

        x = self._embed(params, tokens, dt)
        if cfg.family == "vlm":
            patches = batch["patch_embeds"].astype(dt)
            x = jnp.concatenate([patches, x], axis=1)
            prefix_len = patches.shape[1]
        if cfg.family == "audio":
            enc_out = self._encode(params, batch["frames"].astype(dt))
        b, s = x.shape[0], x.shape[1]
        positions = jnp.arange(s)

        # dtype/shape template from the canonical cache
        template = jax.eval_shape(
            lambda: self.init_cache(b, cache_len, cache_dtype))

        if cfg.homogeneous and not isinstance(params["blocks"], tuple):
            kind = kinds[0]

            def body(carry, layer_params):
                y, aux, st = apply_block_prefill(
                    layer_params, carry, cfg, kind, positions=positions,
                    cache_len=cache_len, prefix_len=prefix_len,
                    enc_out=enc_out, use_rope=(kind != "rwkv"))
                return y, st

            x, states = jax.lax.scan(body, x, params["blocks"])
            cache = jax.tree.map(lambda st, t: st.astype(t.dtype),
                                 states, template)
        else:
            sts = []
            for i, kind in enumerate(kinds):
                x, aux, st = apply_block_prefill(
                    params["blocks"][i], x, cfg, kind, positions=positions,
                    cache_len=cache_len, prefix_len=prefix_len,
                    enc_out=enc_out, use_rope=(kind != "rwkv"))
                sts.append(jax.tree.map(
                    lambda a, t: a.astype(t.dtype), st, template[i]))
            cache = tuple(sts)

        with jax.named_scope("head_loss"):
            x = apply_norm(x, params["final_norm"], cfg.norm)
            logits = _softcap(x[:, -1] @ self._head_w(params, dt),
                              cfg.logit_softcap)
        serve_state = {"cache": cache,
                       "position": jnp.asarray(s, jnp.int32)}
        return logits[:, :cfg.vocab].astype(jnp.float32), serve_state

    def fill_cross_kv(self, params, enc_out, cache):
        """Precompute cross-attention K/V from the encoder memory (once,
        at prefill) into the decode cache — per-token recompute of the
        1500-frame projections dominated whisper decode FLOPs."""
        cfg = self.cfg
        dt = enc_out.dtype
        b, se, _ = enc_out.shape
        hd = cfg.hd
        if cfg.homogeneous and not isinstance(params["blocks"], tuple):
            wk = params["blocks"]["cross"]["k"].astype(dt)   # [L, D, kv*hd]
            wv = params["blocks"]["cross"]["v"].astype(dt)
            ck = jnp.einsum("bed,ldk->lbek", enc_out, wk).reshape(
                cfg.num_layers, b, se, cfg.n_kv, hd)
            cv = jnp.einsum("bed,ldk->lbek", enc_out, wv).reshape(
                cfg.num_layers, b, se, cfg.n_kv, hd)
            cache = dict(cache)
            cache["ck"] = ck.astype(cache["ck"].dtype)
            cache["cv"] = cv.astype(cache["cv"].dtype)
            return cache
        new = []
        for i, st in enumerate(cache):
            p = params["blocks"][i]["cross"]
            st = dict(st)
            st["ck"] = (enc_out @ p["k"].astype(dt)).reshape(
                b, se, cfg.n_kv, hd).astype(st["ck"].dtype)
            st["cv"] = (enc_out @ p["v"].astype(dt)).reshape(
                b, se, cfg.n_kv, hd).astype(st["cv"].dtype)
            new.append(st)
        return tuple(new)

    def decode_step(self, params, tokens, cache, position, enc_out=None,
                    active=None):
        """One serving step: tokens [B, 1] -> (logits [B, V], new cache).

        ``position`` is a scalar (every lane at one position) or a [B]
        vector (one per lane).  ``active`` ([B] bool, optional): lanes
        where it is False keep their recurrent state.  The cache is
        written one token per lane per layer and nowhere else, so a
        donated cache is updated in place.
        """
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        kinds = cfg.layer_kinds
        x = self._embed(params, tokens, dt)

        if cfg.homogeneous and not isinstance(params["blocks"], tuple):
            x, new_cache = decode_stack(params["blocks"], x, cache, cfg,
                                        kinds[0], position=position,
                                        active=active, enc_out=enc_out)
        else:
            new_states = []
            for i, kind in enumerate(kinds):
                x, st = apply_block_decode(
                    params["blocks"][i], x, cache[i], cfg, kind,
                    position=position, enc_out=enc_out,
                    use_rope=(kind not in ("rwkv",)), active=active)
                new_states.append(st)
            new_cache = tuple(new_states)

        with jax.named_scope("head_loss"):
            x = apply_norm(x, params["final_norm"], cfg.norm)
            logits = _softcap(x[:, 0] @ self._head_w(params, dt),
                              cfg.logit_softcap)
        return logits[:, :cfg.vocab].astype(jnp.float32), new_cache


def decode_stack(blocks, x, cache, cfg: LMConfig, kind: str, *, position,
                 active=None, enc_out=None):
    """One token through a scan-stacked layer stack: x [B, 1, D] and the
    stacked cache ([L, B, ...] leaves) -> (y, new cache).

    The cache rides in the scan's carry and each layer writes its own row
    of it in place; it is never sliced out as the scan's input nor
    stacked again as its output."""
    def body(carry, inp):
        h, c = carry
        layer_params, layer = inp
        h, c = apply_block_decode(
            layer_params, h, c, cfg, kind, position=position,
            enc_out=enc_out, use_rope=(kind != "rwkv"), active=active,
            layer=layer)
        return (h, c), None

    n = jax.tree.leaves(blocks)[0].shape[0]
    (x, cache), _ = jax.lax.scan(body, (x, cache),
                                 (blocks, jnp.arange(n)))
    return x, cache
