"""Step builders: the jit-able train / prefill / decode functions.

These are what the launcher jits and the dry-run lowers; all distribution
is expressed through in/out shardings (GSPMD) plus the optional C2P2SL
pipeline (repro/parallel/pipeline.py) over the ``pod`` axis.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.lm import LM
from repro.training.microbatch import microbatched_value_and_grad
from repro.training.optim import Optimizer


def make_lm_loss(model: LM):
    def loss_fn(params, batch):
        loss, mets = model.forward(params, batch)
        return loss, mets
    return loss_fn


def make_lm_train_step(model: LM, opt: Optimizer, *, microbatches: int = 1,
                       pipeline=None, mesh=None, compress: bool = False):
    """Build ``train_step(state_tree, batch) -> (state_tree, metrics)``.

    ``microbatches`` is the paper's k — gradient accumulation over k
    micro-batches (mathematically equivalent update).  ``pipeline`` is an
    optional PipelineSpec that routes the block stack through the C2P2SL
    S-stage pipeline over the pod axis instead (``mesh`` pins the pipeline
    mesh; defaults to the ambient parallel context's).  ``compress``
    applies int8 block-quantized gradients with error feedback before the
    update — the EPSL volume-reduction idea generalized to the DP axis
    (the state tree then carries an ``error_fb`` entry; see
    training/compress.py).
    """
    needs_wire_ef = False
    if pipeline is not None:
        from repro.parallel.pipeline import make_pipelined_loss
        loss_fn = make_pipelined_loss(model, pipeline, mesh=mesh)
        needs_wire_ef = getattr(loss_fn, "needs_wire_ef", False)
        if needs_wire_ef:
            # top-k wire codec: the EF buffer is a third loss input whose
            # gradient IS the updated buffer (pipeline.py) — pull it out
            # alongside the weight grads and write it back to the state.
            vg = jax.value_and_grad(loss_fn, argnums=(0, 2), has_aux=True)
        else:
            vg = jax.value_and_grad(loss_fn, has_aux=True)
    else:
        vg = microbatched_value_and_grad(make_lm_loss(model), microbatches)

    def train_step(state_tree, batch):
        params = state_tree["params"]
        new_state = {}
        if needs_wire_ef:
            (loss, mets), (grads, new_ef) = vg(params, batch,
                                               state_tree["wire_ef"])
            new_state["wire_ef"] = new_ef
        else:
            (loss, mets), grads = vg(params, batch)
        with jax.named_scope("optimizer"):
            if compress:
                from repro.training.compress import (compress_grads,
                                                     decompress_grads)
                qtree, new_efb = compress_grads(
                    jax.tree.map(lambda g: g.astype(jnp.float32), grads),
                    state_tree["error_fb"])
                grads = decompress_grads(qtree)
                new_state["error_fb"] = new_efb
            new_params, new_opt = opt.update(grads, state_tree["opt_state"],
                                             params, state_tree["step"])
        mets = dict(mets)
        mets["loss"] = loss
        new_state.update(params=new_params, opt_state=new_opt,
                         step=state_tree["step"] + 1)
        return new_state, mets

    return train_step


def make_prefill_step(model: LM):
    """prefill(params, batch) -> last-position logits [B, V].

    (The serving path computes hidden states for the whole prompt; emitting
    only the final logits keeps the output small — the cache-filling prefill
    variant lives in serve.py.)
    """
    def prefill(params, batch):
        h = model.hidden(params, batch)
        dt = h.dtype
        logits = h[:, -1] @ model._head_w(params, dt)
        return logits[:, :model.cfg.vocab].astype(jnp.float32)

    return prefill


def make_decode_step(model: LM):
    """decode(params, serve_state, tokens) -> (logits, new serve_state).

    serve_state = {"cache": pytree, "position": int32 scalar}
    (+ "enc_out" for enc-dec models, computed once at prefill).
    """
    def decode(params, serve_state, tokens):
        enc_out = serve_state.get("enc_out")
        logits, new_cache = model.decode_step(
            params, tokens, serve_state["cache"], serve_state["position"],
            enc_out=enc_out)
        new_state = dict(serve_state)
        new_state["cache"] = new_cache
        new_state["position"] = serve_state["position"] + 1
        return logits, new_state

    return decode


def init_serve_state(model: LM, batch: int, cache_len: int,
                     cache_dtype=jnp.bfloat16) -> dict[str, Any]:
    """Decode state: KV caches / recurrent states + position.

    Enc-dec models carry precomputed cross-attention K/V inside the cache
    (fill with ``model.fill_cross_kv(params, enc_out, cache)`` after
    encoding) — the encoder memory itself is NOT needed at decode time.
    """
    return {"cache": model.init_cache(batch, cache_len, cache_dtype),
            "position": jnp.zeros((), jnp.int32)}
