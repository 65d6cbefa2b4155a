"""Architecture registry plumbing: shapes, specs, input stand-ins.

Every assigned architecture contributes an ``ArchSpec`` with
  * ``full``   — the exact published config (dry-run / roofline only),
  * ``smoke``  — a reduced same-family config (CPU tests),
  * ``chip``   — optional: the published widths cut in depth and in the
                 slices held (vocabulary, experts) to one chip's share of
                 a stated deployment; the config's module lists the cuts,
  * ``shapes`` — which of the assigned input shapes apply (with skip reasons).

``input_specs`` builds ``jax.ShapeDtypeStruct`` stand-ins for every model
input of a (config, shape) cell — weak-type-correct, shardable, and never
allocating device memory (the dry-run contract).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.config import LMConfig


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One assigned input shape."""

    name: str
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeSpec("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524_288, 1),
}


#: ``--size`` values the launchers accept.
SIZES = ("smoke", "chip", "full")


@dataclasses.dataclass(frozen=True)
class ArchSpec:
    name: str
    full: LMConfig
    smoke: LMConfig
    # shape name -> None (runs) | str (skip reason)
    skips: dict
    chip: LMConfig | None = None

    def config(self, size: str) -> LMConfig:
        """The config for ``--size``; an architecture without a chip
        share raises instead of falling back to another size."""
        if size not in SIZES:
            raise ValueError(f"size {size!r} not in {SIZES}")
        cfg = getattr(self, size)
        if cfg is None:
            raise ValueError(
                f"{self.name} has no chip-share config (configs/*.py "
                "sets ArchSpec.chip) — use --size smoke or full")
        return cfg

    def applicable(self, shape: str) -> bool:
        return self.skips.get(shape) is None

    def skip_reason(self, shape: str) -> str | None:
        return self.skips.get(shape)


FULL_ATTN_SKIP = ("long_500k needs sub-quadratic attention; this arch is "
                  "pure full/global attention (DESIGN.md §Shape-skips)")
WHISPER_LONG_SKIP = ("whisper decoder context is architecturally 448; the "
                     "encoder is fixed-length — no 500k variant exists")


def no_skips() -> dict:
    return {s: None for s in SHAPES}


def full_attn_skips() -> dict:
    d = no_skips()
    d["long_500k"] = FULL_ATTN_SKIP
    return d


def token_struct(batch: int, seq: int):
    return jax.ShapeDtypeStruct((batch, seq), jnp.int32)


def input_specs(cfg: LMConfig, shape: ShapeSpec, cache_dtype=jnp.bfloat16):
    """ShapeDtypeStruct stand-ins for the step inputs of one cell.

    * train:   {tokens, labels} (+ stub frontend embeddings)
    * prefill: {tokens} (+ frontend)
    * decode:  {tokens [B,1], position scalar} (+ enc_out for enc-dec);
               the KV cache is part of the serve state, built by
               ``cache_specs`` below.
    """
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        out = {"tokens": token_struct(b, s), "labels": token_struct(b, s)}
    elif shape.kind == "prefill":
        out = {"tokens": token_struct(b, s)}
    else:  # decode: one new token against a cache of seq_len
        out = {"tokens": token_struct(b, 1)}
    if cfg.family == "vlm" and shape.kind != "decode":
        out["patch_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.num_patches, cfg.d_model), jnp.float32)
    if cfg.family == "audio":
        # stub conv frontend: precomputed frame embeddings
        out["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.enc_seq, cfg.d_model), jnp.float32)
    return out


def cache_specs(cfg: LMConfig, shape: ShapeSpec, cache_dtype=jnp.bfloat16):
    """ShapeDtypeStructs of the decode cache (KV / recurrent state)."""
    from repro.models.lm import LM
    model = LM(cfg)
    return jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                 cache_dtype))


def param_specs(cfg: LMConfig):
    """ShapeDtypeStructs of the parameter tree (no allocation)."""
    from repro.models.lm import LM
    model = LM(cfg)
    return jax.eval_shape(lambda: model.init(jax.random.key(0)))
