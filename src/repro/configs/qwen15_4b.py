"""qwen1.5-4b [dense] — 40L d_model=2560 20H (MHA: kv=20) d_ff=6912
vocab=151936, QKV bias, SiLU-gated MLP, RMSNorm, untied embeddings.

Source: Qwen/Qwen1.5-4B's published ``config.json`` (hidden_size 2560,
intermediate_size 6912, num_attention_heads 20, num_key_value_heads 20,
num_hidden_layers 40, vocab_size 151936, tie_word_embeddings false).

``CHIP`` is one TPU v5e chip's share of a stated deployment: the 40
layers run as 10 pipeline stages of 4 layers, and the embedding and the
head are split by vocabulary over 8 chips.  So a chip holds 4 layers and
an eighth of the vocabulary; every width is the published one.  Token
ids are drawn from the slice, and the head, the loss and sampling run
over it.
"""
import dataclasses

from repro.configs.base import ArchSpec, full_attn_skips
from repro.models.config import LMConfig

FULL = LMConfig(
    name="qwen1.5-4b",
    family="dense",
    num_layers=40,
    d_model=2560,
    n_heads=20,
    n_kv=20,
    d_ff=6912,
    vocab=151_936,
    qkv_bias=True,
    act="silu",
    norm="rmsnorm",
    rope_theta=1_000_000.0,
)

#: Keys of ``CHIP`` cut from ``FULL``: published value, value held here,
#: and why.
REDUCED = {
    "num_layers": (40, 4, "one of 10 pipeline stages of 4 layers"),
    "vocab": (151_936, 151_936 // 8, "one of 8 vocabulary slices of the "
              "embedding and the head"),
}

CHIP = dataclasses.replace(
    FULL, name="qwen1.5-4b-chip",
    **{key: held for key, (_, held, _) in REDUCED.items()})

#: Sizes set here that the source does not fix.
ASSUMED = {
    "rope_theta": "1e6, as in FULL; not re-read from the 4B config.json",
    "dtype": "bfloat16 compute over float32 master weights",
    "weights": "random from the run's seed",
}

SMOKE = LMConfig(
    name="qwen1.5-smoke",
    family="dense",
    num_layers=2,
    d_model=64,
    n_heads=4,
    n_kv=4,
    d_ff=128,
    vocab=512,
    qkv_bias=True,
    act="silu",
    norm="rmsnorm",
    rope_theta=1_000_000.0,
    dtype="float32",
)

SPEC = ArchSpec(name="qwen1.5-4b", full=FULL, smoke=SMOKE,
                skips=full_attn_skips(), chip=CHIP)
