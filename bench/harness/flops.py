"""Operations and bytes of the work, computed from shapes.

Counts are of the work the model needs, whatever implements it: no
recomputation, no masked pipeline ticks, and one copy of a replicated
head.  ``cfg`` is a configuration file's dict (Hugging Face keys) of a
dense decoder; a family module (``bench/families/``) serves these counts
for its configurations or counts its own.  ``codec_bytes`` is the hop's,
whatever the family.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights that multiply every token: the blocks' projections and the
    head (the embedding is a lookup)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def stored_params(cfg: dict) -> int:
    """Every stored weight: the matmul weights, biases, norms, embedding."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    bias = (q + 2 * kv) if cfg.get("attention_bias") else 0
    per_layer = bias + 2 * d
    return (matmul_params(cfg) + cfg["num_hidden_layers"] * per_layer
            + d + cfg["vocab_size"] * d)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward: 6 per matmul weight, plus causal attention's
    score and value products, 6 * layers * seq * d_model (on average a
    token attends to seq/2 positions; 2 products of 2 FLOPs each per
    position and width, times 3 for the backward)."""
    d = cfg["hidden_size"]
    return 6.0 * matmul_params(cfg) + 6.0 * cfg["num_hidden_layers"] * seq * d


def kv_bytes_per_token(cfg: dict, itemsize: int) -> int:
    """Keys and values of one position over all layers."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * hd
            * itemsize)


def decode_step_least(cfg: dict, positions, weight_itemsize: int,
                      kv_itemsize: int) -> tuple:
    """(FLOPs, bytes) one decode step needs for active lanes at
    ``positions`` (each lane's position before the step): every weight
    that multiplies a token read once as stored, each lane's keys and
    values up to and including its position read once, and its new
    position written."""
    lanes = len(positions)
    ctx = sum(int(p) + 1 for p in positions)
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    attn = 4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * hd * ctx
    flops = 2.0 * matmul_params(cfg) * lanes + attn
    kv = kv_bytes_per_token(cfg, kv_itemsize)
    nbytes = matmul_params(cfg) * weight_itemsize + kv * ctx
    return flops, nbytes


def codec_bytes(rows: int, d: int, block: int, in_itemsize: int,
                code_itemsize: int = 1) -> int:
    """One encode (or one decode) of a [rows, d] activation with one
    float32 scale per ``block`` values: read one side, write the other."""
    return rows * d * (in_itemsize + code_itemsize) + rows * (d // block) * 4


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Forward of one prompt: 2 per block matmul weight per token, causal
    attention, and the head for the last position, the only one whose
    logits a prefill needs."""
    d = cfg["hidden_size"]
    head = d * cfg["vocab_size"]
    attn = 2.0 * cfg["num_hidden_layers"] * d * prompt_len * prompt_len
    return 2.0 * (matmul_params(cfg) - head) * prompt_len + 2.0 * head + attn
