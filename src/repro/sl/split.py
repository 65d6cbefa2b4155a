"""Cut-layer model splitting: UE-side / BS-side submodels.

A ``SplitSpec`` turns one model into the two stage functions of split
learning.  ResNet-18 cuts at the Table II unit boundaries; LMs cut at a
transformer block index (embedding lives UE-side, head BS-side) — the same
abstraction the TPU pipeline (repro/parallel/pipeline.py) uses.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.models import resnet


@dataclasses.dataclass(frozen=True)
class SplitSpec:
    """ue_fwd(ue_params, batch_inputs) -> activations
    bs_loss(bs_params, activations, labels) -> (loss, metrics)"""
    ue_fwd: Callable
    bs_loss: Callable
    split_params: Callable      # full params -> (ue_params, bs_params)
    merge_params: Callable      # (ue, bs) -> full


def resnet_split(l: int) -> SplitSpec:
    """Cut ResNet-18 after Table II unit ``l`` (1..5)."""
    assert 1 <= l <= 5

    def ue_fwd(ue_params, images):
        return resnet.forward_cut(ue_params, images, 0, l)

    def bs_loss(bs_params, acts, labels):
        logits = resnet.forward_cut(bs_params, acts, l, 6)
        ll = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.take_along_axis(ll, labels[:, None], axis=1).mean()
        acc = (logits.argmax(-1) == labels).mean()
        return loss, {"acc": acc}

    keys_ue, keys_bs = _resnet_key_split(l)

    def split_params(params):
        return ({k: params[k] for k in keys_ue if k in params},
                {k: params[k] for k in keys_bs if k in params})

    def merge_params(ue, bs):
        return {**ue, **bs}

    return SplitSpec(ue_fwd, bs_loss, split_params, merge_params)


def _resnet_key_split(l: int):
    all_keys = (["conv1", "g1w", "g1b"], ["stage0"], ["stage1"], ["stage2"],
                ["stage3"], ["fc_w", "fc_b"])
    ue, bs = [], []
    for u, ks in enumerate(all_keys):
        (ue if u < l else bs).extend(ks)
    return ue, bs


def lm_split(model, l: int) -> SplitSpec:
    """Cut an LM after block ``l``: UE = embed + blocks[:l]; BS = rest+head.

    Requires a homogeneous (scan-stacked) architecture.
    """
    cfg = model.cfg
    assert cfg.homogeneous, "lm_split requires a homogeneous layer stack"
    assert 1 <= l < cfg.num_layers

    def split_params(params):
        blocks = params["blocks"]
        take = lambda tree, sl: jax.tree.map(lambda a: a[sl], tree)
        ue = {"embed": params["embed"], "blocks": take(blocks, slice(0, l))}
        bs = {"blocks": take(blocks, slice(l, cfg.num_layers)),
              "final_norm": params["final_norm"]}
        if "head" in params:
            bs["head"] = params["head"]
        return ue, bs

    def merge_params(ue, bs):
        blocks = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0),
                              ue["blocks"], bs["blocks"])
        out = {"embed": ue["embed"], "blocks": blocks,
               "final_norm": bs["final_norm"]}
        if "head" in bs:
            out["head"] = bs["head"]
        return out

    def ue_fwd(ue_params, tokens):
        dt = jnp.dtype(cfg.dtype)
        x = model._embed({"embed": ue_params["embed"]}, tokens, dt)
        positions = jnp.arange(x.shape[1])
        from repro.models.blocks import apply_block

        def body(carry, layer_params):
            y, _ = apply_block(layer_params, carry, cfg, cfg.layer_kinds[0],
                               positions=positions)
            return y, None

        x, _ = jax.lax.scan(body, x, ue_params["blocks"])
        return x

    def bs_loss(bs_params, acts, labels):
        from repro.models.blocks import apply_block
        positions = jnp.arange(acts.shape[1])

        def body(carry, layer_params):
            y, _ = apply_block(layer_params, carry, cfg, cfg.layer_kinds[0],
                               positions=positions)
            return y, None

        x, _ = jax.lax.scan(body, acts, bs_params["blocks"])
        if cfg.tie_embeddings:
            raise ValueError("tied embeddings cannot be split at the head")
        loss = model.head_loss(bs_params, x, labels)
        return loss, {"xent": loss}

    return SplitSpec(ue_fwd, bs_loss, split_params, merge_params)
