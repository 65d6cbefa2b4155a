"""Qwen2 decoders (``model_type`` "qwen2": Qwen1.5 and Qwen2): dense
layers of RMSNorm, rotary attention with q/k/v biases and a SiLU-gated
MLP, and an untied head.

The plain reference is ``harness/reference.py`` and the FLOP counts are
``harness/flops.py``'s; this file maps a configuration onto the program
and names the published widths.
"""
from harness import flops, reference

param_shapes = reference.param_shapes
train_flops_per_token = flops.train_flops_per_token
decode_step_least = flops.decode_step_least
prefill_flops = flops.prefill_flops


def Reference(config: dict, seed: int, devices, *, microbatches: int = 1,
              **options):
    """``reference.Reference``.  The loss is a mean over tokens, which
    equal micro-batches average to, so ``microbatches`` changes nothing."""
    return reference.Reference(config, seed, devices, **options)


def lm_config(config: dict, name: str = "bench"):
    """The program's ``LMConfig`` for a configuration file written with
    the Hugging Face ``config.json`` keys of a dense decoder."""
    from repro.models.config import LMConfig
    return LMConfig(
        name=name, family="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab=config["vocab_size"],
        qkv_bias=bool(config["attention_bias"]),
        act=config["hidden_act"], norm="rmsnorm",
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=config["torch_dtype"])


def published(config: dict) -> dict:
    """The published sizes of the model that ``source`` names, as the
    program's registry holds them: the entry named by the source's
    repository, lower-cased (``.../Qwen/Qwen1.5-4B/...``: ``qwen1.5-4b``)."""
    from repro.configs import get_arch
    repo = config["source"].split("huggingface.co/", 1)[1].split("/")[1]
    full = get_arch(repo.lower()).full
    return {"hidden_size": full.d_model, "intermediate_size": full.d_ff,
            "num_attention_heads": full.n_heads,
            "num_key_value_heads": full.n_kv,
            "num_hidden_layers": full.num_layers, "vocab_size": full.vocab,
            "tie_word_embeddings": full.tie_embeddings}
