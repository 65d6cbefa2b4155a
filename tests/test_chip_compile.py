"""The fused wire codec compiled for a TPU v5e that is described, not
attached: Mosaic's checks (block tiling, VMEM) run here at the real hop
widths, where interpret mode accepts every shape.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.  The fixture skips where no v5e can be described.
"""
import importlib.util
import os

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
