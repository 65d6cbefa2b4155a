"""The fused wire codec compiled for a TPU v5e that is described, not
attached: Mosaic's checks (block tiling, VMEM) run here at the real hop
widths, where interpret mode accepts every shape.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.  The fixture skips where no v5e can be described.
"""
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
