"""The fused wire codec and the blocked attention kernel compiled for a
TPU v5e that is described, not attached: Mosaic's checks (block tiling,
VMEM) run here at the real widths, where interpret mode accepts every
shape.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.  The fixture skips where no v5e can be described.
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.wire_codec import decode_fused, encode_fused

D_MODEL = 2560   # qwen1.5-4b's published width

#: (leading dims of the hop activation [..., d_model], its dtype): the
#: pipeline hop at mb 2 x seq 4096; a ragged 4094-row hop (no multiple-of-8 divisor <= 128, so the
#: grid ends in a partial block); an odd INFER prompt chunk.
SHAPES = [pytest.param((2, 4096), jnp.bfloat16, id="8192rows-bf16"),
          pytest.param((2, 2047), jnp.bfloat16, id="4094rows-bf16"),
          pytest.param((1, 250), jnp.float32, id="250rows-f32")]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("wire_dtype", ["int8", "fp8"])
@pytest.mark.parametrize("lead,dtype", SHAPES)
def test_fused_codec_compiles_for_v5e(one_chip, lead, dtype, wire_dtype):
    x = jax.ShapeDtypeStruct(lead + (D_MODEL,), dtype, sharding=one_chip)
    enc = jax.jit(lambda x: encode_fused(x, wire_dtype)).lower(x).compile()
    assert "tpu_custom_call" in enc.as_text()
    q, s = jax.eval_shape(lambda x: encode_fused(x, wire_dtype), x)
    q, s = (jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in (q, s))
    dec = jax.jit(lambda q, s: decode_fused(q, s, dtype)).lower(q, s).compile()
    assert "tpu_custom_call" in dec.as_text()


@pytest.mark.parametrize("wire_dtype", ["int8", "fp8"])
def test_codec_kernels_keep_their_names_for_the_benchmark(one_chip,
                                                          monkeypatch,
                                                          wire_dtype):
    """The codec's custom calls are named ``wire_encode``/``wire_decode``,
    and the benchmark's ``wire_codec_roofline`` still tells them apart
    by their operand and result types, as a trace names them."""
    bench = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                         "bench")
    monkeypatch.syspath_prepend(bench)
    from harness import trace
    spec = importlib.util.spec_from_file_location(
        "wire_codec_roofline",
        os.path.join(bench, "metrics", "wire_codec_roofline.py"))
    roofline = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(roofline)

    x = jax.ShapeDtypeStruct((2, 4096, D_MODEL), jnp.bfloat16,
                             sharding=one_chip)
    enc = jax.jit(lambda x: encode_fused(x, wire_dtype)).lower(x).compile()
    q, s = jax.eval_shape(lambda x: encode_fused(x, wire_dtype), x)
    q, s = (jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in (q, s))
    dec = jax.jit(lambda q, s: decode_fused(q, s, jnp.bfloat16)).lower(
        q, s).compile()
    for compiled, name, match in ((enc, "wire_encode", roofline.is_encode),
                                  (dec, "wire_decode", roofline.is_decode)):
        calls = [trace.short_name(line.strip())
                 for line in compiled.as_text().splitlines()
                 if "custom_call_target=\"tpu_custom_call\"" in line]
        assert calls and all(c.startswith(name + ".") for c in calls), calls
        assert all(match(c) for c in calls), calls
        assert not any(roofline.is_encode(c) and roofline.is_decode(c)
                       for c in calls)


#: qwen1.5-4b's attention on one micro-batch of the training cells:
#: 20 heads of 128 over 4,096 tokens, and a GQA grouping of the same
ATTN_SHAPES = [pytest.param((1, 4096, 20, 128), 20, id="mha-4096x20x128"),
               pytest.param((1, 4096, 20, 128), 4, id="gqa-4096x20:4x128")]


@pytest.mark.parametrize("q_shape,n_kv", ATTN_SHAPES)
def test_causal_attention_compiles_for_v5e(one_chip, q_shape, n_kv):
    """The blocked attention kernel, forward and both backward kernels,
    at the real width: Mosaic's tiling and VMEM checks."""
    from repro.kernels.causal_attention import causal_attention

    b, s, _, dh = q_shape
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, n_kv, dh), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    kernels = {n.split("_no_residuals")[0].split("_residuals")[0]
               for n in re.findall(r"(splash_\w+)", text)}
    assert {"splash_mha_fwd", "splash_mha_dq", "splash_mha_dkv"} <= kernels \
        or {"splash_mqa_fwd", "splash_mqa_dq", "splash_mqa_dkv"} <= kernels, \
        kernels


def test_attention_kernel_calls_carry_the_attention_scope(one_chip,
                                                          monkeypatch):
    """In a train step compiled for the chip with the kernel path, every
    custom call of the attention kernel (forward, recomputed forward,
    dQ and dKV) carries the ``attention`` scope, as the benchmark's
    scope table reads it: ``attention_ms`` keeps counting its time."""
    from repro.kernels import ops
    from repro.models import LM, LMConfig, common
    from repro.parallel.steps import make_lm_train_step
    from repro.training.optim import adamw

    bench = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                         "bench")
    monkeypatch.syspath_prepend(bench)
    from harness import scopes

    monkeypatch.setattr(common, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = LMConfig(name="t", num_layers=2, d_model=256, n_heads=2, n_kv=2,
                   d_ff=512, vocab=256, qkv_bias=True, dtype="bfloat16")
    model, opt = LM(cfg), adamw(1e-3)
    state = jax.eval_shape(lambda: (lambda p: {
        "params": p, "opt_state": opt.init(p),
        "step": jnp.zeros((), jnp.int32)})(model.init(jax.random.key(0))))
    state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), state)
    tok = jax.ShapeDtypeStruct((4, 512), jnp.int32, sharding=one_chip)
    with common.AttentionPaths() as paths:
        text = jax.jit(make_lm_train_step(model, opt, microbatches=2)).lower(
            state, {"tokens": tok, "labels": tok}).compile().as_text()
    assert set(paths) == {"kernel"}
    table = scopes.scope_table(text)
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "splash_" in line]
    names = {re.search(r"(splash_\w+)", c).group(1) for c in calls}
    assert any("fwd" in n for n in names) and any("dq" in n for n in names) \
        and any("dkv" in n for n in names), names
    for call in calls:
        instr = trace_name(call)
        assert table[instr] == "attention", (instr, table[instr])


def trace_name(line: str) -> str:
    """The instruction name of an HLO line, as a trace names the op."""
    from harness import trace
    return trace.short_name(line).split(" ", 1)[0]
