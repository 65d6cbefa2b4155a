"""Split-inference serving: UE runs the sub-cut layers, BS the rest.

The training runtime (``repro/runtime/``) already ships coded cut
ACTIVATIONS up and gradients down over a real loopback socket; this
module carries the same wire to the serving path — millions of users is
inference traffic, and EPSL-style parallel SL serves the same split
model for both learning and inference:

* ``SplitDecode`` cuts a homogeneous decoder-only LM after block ``l``
  into a UE half (embed + blocks[:l], with its OWN slice of the decode
  cache) and a BS half (blocks[l:] + final norm + head, with the other
  cache slice) — composing the two halves is the monolithic
  ``prefill_with_cache`` / ``decode_step`` exactly (same scan, split in
  two), which the tests pin.
* ``run_split_infer`` drives a real asyncio loopback socket: the UE
  prefills its half, ships the coded cut activation of the WHOLE prompt
  as one INFER frame (``parallel/wire.py`` dense grammar — none / int8
  / fp8; activations are forward-only, so no top-k and no error
  feedback), then per decode step ships one coded ``[B, 1, d]``
  activation and receives the sampled token back.  The BS replies with
  the token as an aux (un-billed) section, samples greedily, and audits
  every uplink's measured payload bytes against
  ``protocol.billed_hop_bytes`` — the planner's
  ``autotune.wire_bytes_per_element`` billing, held to 1% on the real
  socket.
"""
from __future__ import annotations

import asyncio

import numpy as np


def _require_dense(wire_dtype: str) -> str:
    """INFER hops carry forward activations only: dense codecs."""
    from repro.parallel.wire import parse_wire_dtype
    base, frac = parse_wire_dtype(wire_dtype)
    if frac is not None:
        raise ValueError(
            f"wire_dtype {wire_dtype!r}: the INFER hop is forward-only "
            "(no gradient, no error feedback) — top-k sparsification "
            "would silently corrupt activations; use 'none', 'int8' or "
            "'fp8'")
    return base


class SplitDecode:
    """Cut a homogeneous decoder-only LM after block ``l`` for serving.

    UE = embed + blocks[:l]; BS = blocks[l:] + final_norm + head.  Both
    halves hold THEIR OWN layers' slice of the decode cache; composing
    ``ue_*`` then ``bs_*`` reproduces the monolithic serving step (same
    per-layer ops in the same order — the split is only in who holds
    which scan segment).
    """

    def __init__(self, model, l: int):
        import jax

        cfg = model.cfg
        if not cfg.homogeneous:
            raise ValueError("SplitDecode requires a homogeneous stack")
        if cfg.tie_embeddings:
            raise ValueError("tied embeddings cannot be split at the head")
        if getattr(cfg, "enc_layers", 0) or cfg.family in ("audio", "vlm"):
            raise ValueError(
                f"SplitDecode serves decoder-only token LMs, not "
                f"{cfg.family}")
        if not 1 <= l < cfg.num_layers:
            raise ValueError(
                f"cut l={l} must be in [1, {cfg.num_layers})")
        self.model = model
        self.cfg = cfg
        self.l = int(l)
        self.kind = cfg.layer_kinds[0]
        self._jax = jax

    def split_params(self, params):
        jax = self._jax
        l, cfg = self.l, self.cfg
        take = lambda tree, sl: jax.tree.map(lambda a: a[sl], tree)
        ue = {"embed": params["embed"],
              "blocks": take(params["blocks"], slice(0, l))}
        bs = {"blocks": take(params["blocks"], slice(l, cfg.num_layers)),
              "final_norm": params["final_norm"],
              "head": params["head"]}
        return ue, bs

    # -- cache ---------------------------------------------------------------

    def _half_cache(self, n_layers, batch, cache_len, dtype):
        import jax.numpy as jnp

        from repro.models.blocks import init_block_state
        one = init_block_state(self.cfg, self.kind, batch, cache_len,
                               dtype)
        return self._jax.tree.map(
            lambda a: jnp.broadcast_to(a[None],
                                       (n_layers,) + a.shape), one)

    # -- UE half -------------------------------------------------------------

    def ue_prefill(self, ue_params, tokens, *, cache_len,
                   cache_dtype=None):
        """tokens [B, S] -> (cut activations [B, S, d], ue cache)."""
        import jax
        import jax.numpy as jnp

        from repro.models.blocks import apply_block_prefill
        cfg, kind = self.cfg, self.kind
        cache_dtype = cache_dtype or jnp.float32
        dt = jnp.dtype(cfg.dtype)
        x = self.model._embed({"embed": ue_params["embed"]}, tokens, dt)
        b, s = x.shape[0], x.shape[1]
        positions = jnp.arange(s)
        template = jax.eval_shape(
            lambda: self._half_cache(self.l, b, cache_len, cache_dtype))

        def body(carry, layer_params):
            y, _aux, st = apply_block_prefill(
                layer_params, carry, cfg, kind, positions=positions,
                cache_len=cache_len, use_rope=(kind != "rwkv"))
            return y, st

        x, states = jax.lax.scan(body, x, ue_params["blocks"])
        cache = jax.tree.map(lambda st, t: st.astype(t.dtype), states,
                             template)
        return x, cache

    def ue_decode(self, ue_params, tok, cache, position):
        """tok [B, 1] -> (cut activation [B, 1, d], new ue cache)."""
        import jax.numpy as jnp

        from repro.models.lm import decode_stack
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        x = self.model._embed({"embed": ue_params["embed"]}, tok, dt)
        return decode_stack(ue_params["blocks"], x, cache, cfg, self.kind,
                            position=position)

    # -- BS half -------------------------------------------------------------

    def bs_prefill(self, bs_params, acts, *, cache_len,
                   cache_dtype=None):
        """Cut activations [B, S, d] -> (last-position logits [B, V],
        bs cache)."""
        import jax
        import jax.numpy as jnp

        from repro.models.blocks import apply_block_prefill
        from repro.models.common import apply_norm
        from repro.models.lm import _softcap
        cfg, kind = self.cfg, self.kind
        cache_dtype = cache_dtype or jnp.float32
        dt = jnp.dtype(cfg.dtype)
        x = acts.astype(dt)
        b, s = x.shape[0], x.shape[1]
        positions = jnp.arange(s)
        n_bs = cfg.num_layers - self.l
        template = jax.eval_shape(
            lambda: self._half_cache(n_bs, b, cache_len, cache_dtype))

        def body(carry, layer_params):
            y, _aux, st = apply_block_prefill(
                layer_params, carry, cfg, kind, positions=positions,
                cache_len=cache_len, use_rope=(kind != "rwkv"))
            return y, st

        x, states = jax.lax.scan(body, x, bs_params["blocks"])
        cache = jax.tree.map(lambda st, t: st.astype(t.dtype), states,
                             template)
        x = apply_norm(x, bs_params["final_norm"], cfg.norm)
        logits = _softcap(x[:, -1] @ bs_params["head"].astype(dt),
                          cfg.logit_softcap)
        return logits[:, :cfg.vocab].astype(jnp.float32), cache

    def bs_decode(self, bs_params, act, cache, position):
        """Cut activation [B, 1, d] -> (logits [B, V], new bs cache)."""
        import jax.numpy as jnp

        from repro.models.common import apply_norm
        from repro.models.lm import _softcap, decode_stack
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        x, new_cache = decode_stack(bs_params["blocks"], act.astype(dt),
                                    cache, cfg, self.kind,
                                    position=position)
        x = apply_norm(x, bs_params["final_norm"], cfg.norm)
        logits = _softcap(x[:, 0] @ bs_params["head"].astype(dt),
                          cfg.logit_softcap)
        return logits[:, :cfg.vocab].astype(jnp.float32), new_cache


# ---------------------------------------------------------------------------
# Loopback split-inference serving (INFER frames on a real socket).
# ---------------------------------------------------------------------------


class BSInferServer:
    """BS side: receives coded cut activations, runs blocks[l:], samples
    greedily, replies the token; audits wire honesty per uplink frame."""

    def __init__(self, split: SplitDecode, bs_params, *, cache_len: int,
                 wire_dtype: str = "none", shaper=None, qos=None,
                 host: str = "127.0.0.1", port: int = 0):
        import jax
        _require_dense(wire_dtype)
        self.split = split
        self.bs_params = bs_params
        self.cache_len = int(cache_len)
        self.wire_dtype = str(wire_dtype)
        self.shaper = shaper
        self.qos = qos
        self.host, self.port = host, int(port)
        self._server = None
        # (measured payload bytes, billed bytes) per uplink frame
        self.audit: list[tuple] = []
        self._prefill = jax.jit(
            lambda p, a: split.bs_prefill(p, a, cache_len=self.cache_len))
        self._decode = jax.jit(split.bs_decode)

    async def start(self):
        self._server = await asyncio.start_server(
            self._handle, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        return self.host, self.port

    async def close(self):
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()

    async def _reply_tok(self, writer, cid, step, tok):
        from repro.runtime import protocol
        frame = protocol.pack_frame(
            protocol.INFER, cid, step, meta={"phase": "tok"},
            arrays={"tok": np.asarray(tok, np.int32)})
        if self.shaper is not None:
            await asyncio.sleep(self.shaper.delay_s(len(frame)))
        writer.write(frame)
        await writer.drain()

    def _audit_uplink(self, frame) -> None:
        from repro.runtime import protocol
        shape = frame.meta["shape"]
        n_elements = int(np.prod(shape))
        act_bytes = np.dtype(frame.meta["dtype"]).itemsize
        billed = protocol.billed_hop_bytes(
            n_elements, shape[-1], frame.meta["codec"], act_bytes)
        self.audit.append((frame.payload_nbytes, billed))
        if self.qos is not None:
            self.qos.record_arrival(frame.client, frame.wire_nbytes,
                                    frame.payload_nbytes,
                                    frame.aux_nbytes)

    async def _handle(self, reader, writer):
        import jax.numpy as jnp

        from repro.runtime import protocol
        cache = None
        position = None
        # every exit closes our end: ``Server.wait_closed()`` (Python >=
        # 3.12.1) waits for every connection to drop
        try:
            hello = await protocol.read_frame(reader)
            if hello.ftype != protocol.HELLO:
                raise ValueError(
                    f"handshake must be HELLO, got ftype={hello.ftype}")
            if hello.meta.get("wire_dtype",
                              self.wire_dtype) != self.wire_dtype:
                raise ValueError(
                    f"client codec {hello.meta.get('wire_dtype')!r} != "
                    f"server {self.wire_dtype!r}")
            cid = hello.client
            while True:
                frame = await protocol.read_frame(reader)
                if frame.ftype == protocol.BYE:
                    break
                if frame.ftype != protocol.INFER:
                    raise ValueError(
                        f"expected INFER frame, got ftype={frame.ftype}")
                self._audit_uplink(frame)
                acts = jnp.asarray(protocol.decode_act_payload(frame))
                if frame.meta["phase"] == "prefill":
                    logits, cache = self._prefill(self.bs_params, acts)
                    position = acts.shape[1]
                else:
                    logits, cache = self._decode(
                        self.bs_params, acts, cache,
                        jnp.asarray(position, jnp.int32))
                    position += 1
                tok = np.asarray(jnp.argmax(logits, axis=-1),
                                 np.int32)[:, None]
                await self._reply_tok(writer, cid, frame.step, tok)
        finally:
            writer.close()


class UEInferClient:
    """UE side: prefills blocks[:l], then streams one coded cut
    activation per decode step and feeds the returned token back."""

    def __init__(self, client_id: int, split: SplitDecode, ue_params, *,
                 cache_len: int, wire_dtype: str = "none", shaper=None):
        import jax
        _require_dense(wire_dtype)
        self.client_id = int(client_id)
        self.split = split
        self.ue_params = ue_params
        self.cache_len = int(cache_len)
        self.wire_dtype = str(wire_dtype)
        self.shaper = shaper
        self.sent_payload_bytes = 0
        self._prefill = jax.jit(
            lambda p, t: split.ue_prefill(p, t, cache_len=self.cache_len))
        self._decode = jax.jit(split.ue_decode)

    async def _send(self, writer, payload: bytes):
        if self.shaper is not None:
            await asyncio.sleep(self.shaper.delay_s(len(payload)))
        writer.write(payload)
        await writer.drain()

    async def run(self, host: str, port: int, prompts, gen: int):
        """prompts [B, L] int32 -> emitted tokens [B, gen] (the BS's
        greedy chain; the prefill seed token is fed, not emitted)."""
        import jax.numpy as jnp

        from repro.runtime import protocol
        prompts = np.asarray(prompts, np.int32)
        reader, writer = await asyncio.open_connection(host, port)
        cid = self.client_id
        try:
            await self._send(writer, protocol.pack_frame(
                protocol.HELLO, cid, 0,
                meta={"wire_dtype": self.wire_dtype, "mode": "infer"}))
            acts, cache = self._prefill(self.ue_params,
                                        jnp.asarray(prompts))
            position = prompts.shape[1]
            arrays, meta = protocol.encode_act_payload(
                np.asarray(acts), self.wire_dtype)
            frame = protocol.pack_frame(
                protocol.INFER, cid, 0, meta=dict(meta, phase="prefill"),
                arrays=arrays)
            self.sent_payload_bytes += sum(
                a.nbytes for k, a in arrays.items()
                if k in protocol.PAYLOAD_SECTIONS)
            await self._send(writer, frame)
            out = []
            for step in range(1, gen + 1):
                reply = await protocol.read_frame(reader)
                if reply.ftype != protocol.INFER \
                        or reply.meta.get("phase") != "tok":
                    raise ValueError(f"expected tok reply, got {reply}")
                tok = reply.arrays["tok"].astype(np.int32)
                if step > 1:
                    out.append(tok[:, 0])
                act, cache = self._decode(
                    self.ue_params, jnp.asarray(tok), cache,
                    jnp.asarray(position, jnp.int32))
                position += 1
                arrays, meta = protocol.encode_act_payload(
                    np.asarray(act), self.wire_dtype)
                self.sent_payload_bytes += sum(
                    a.nbytes for k, a in arrays.items()
                    if k in protocol.PAYLOAD_SECTIONS)
                await self._send(writer, protocol.pack_frame(
                    protocol.INFER, cid, step,
                    meta=dict(meta, phase="decode"), arrays=arrays))
            # one reply is still in flight: the token of the last decode
            reply = await protocol.read_frame(reader)
            out.append(reply.arrays["tok"][:, 0].astype(np.int32))
            await self._send(writer, protocol.pack_frame(
                protocol.BYE, cid, gen))
            return np.stack(out, axis=1)
        finally:
            writer.close()


async def _run_split_infer(model, params, *, cut, prompts, gen,
                           cache_len, wire_dtype="none", shaper=None,
                           qos=None):
    split = SplitDecode(model, cut)
    ue_params, bs_params = split.split_params(params)
    server = BSInferServer(split, bs_params, cache_len=cache_len,
                           wire_dtype=wire_dtype, shaper=shaper, qos=qos)
    host, port = await server.start()
    client = UEInferClient(0, split, ue_params, cache_len=cache_len,
                           wire_dtype=wire_dtype, shaper=shaper)
    try:
        tokens = await client.run(host, port, prompts, gen)
    finally:
        await server.close()
    measured = sum(m for m, _ in server.audit)
    billed = sum(b for _, b in server.audit)
    return {"tokens": tokens,
            "measured_payload_bytes": int(measured),
            "billed_payload_bytes": float(billed),
            "frames": len(server.audit),
            "client_payload_bytes": int(client.sent_payload_bytes)}


def run_split_infer(model, params, *, cut: int, prompts, gen: int,
                    cache_len: int, wire_dtype: str = "none",
                    shaper=None, qos=None) -> dict:
    """Serve ``prompts`` for ``gen`` greedy tokens through the split
    UE->BS loopback; returns tokens + the wire-honesty audit sums."""
    return asyncio.run(_run_split_infer(
        model, params, cut=cut, prompts=prompts, gen=gen,
        cache_len=cache_len, wire_dtype=wire_dtype, shaper=shaper,
        qos=qos))
