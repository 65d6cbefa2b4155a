"""The trip-count-aware HLO static analyzer (the roofline's data source)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo_costs import (analyze, computation_multipliers,
                                      flat_cost_analysis, parse_hlo)
from repro.analysis.roofline import HW, RooflineTerms, model_flops_for
from repro.configs import SHAPES, get_arch


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_scan_trip_count_correction():
    def body(x, w):
        return x @ w, None
    W = jnp.zeros((8, 256, 256), jnp.float32)
    x = jnp.zeros((4, 256), jnp.float32)
    c = _compile(lambda x, W: jax.lax.scan(body, x, W)[0], x, W)
    res = analyze(c.as_text())
    assert res["flops"] == pytest.approx(8 * 2 * 4 * 256 * 256)
    # the flat XLA number misses the trip count (the bug we correct)
    flat = float(flat_cost_analysis(c).get("flops", 0.0))
    assert flat < res["flops"] / 4


def test_nested_scan_multipliers():
    def body(x, w):
        return x @ w, None
    W = jnp.zeros((8, 256, 256), jnp.float32)
    x = jnp.zeros((4, 256), jnp.float32)

    def outer(x, W):
        def ob(x, _):
            return jax.lax.scan(body, x, W)[0], None
        return jax.lax.scan(ob, x, jnp.arange(3))[0]

    res = analyze(_compile(outer, x, W).as_text())
    assert res["flops"] == pytest.approx(3 * 8 * 2 * 4 * 256 * 256)


def test_dot_flops_with_contraction():
    a = jnp.zeros((32, 64), jnp.float32)
    b = jnp.zeros((64, 16), jnp.float32)
    res = analyze(_compile(lambda a, b: a @ b, a, b).as_text())
    assert res["flops"] == pytest.approx(2 * 32 * 16 * 64)


def test_traffic_counts_dot_operands():
    a = jnp.zeros((128, 256), jnp.float32)
    b = jnp.zeros((256, 128), jnp.float32)
    res = analyze(_compile(lambda a, b: a @ b, a, b).as_text())
    expect = (128 * 256 + 256 * 128 + 128 * 128) * 4
    assert res["bytes"] >= expect
    assert res["bytes"] <= 3 * expect


def test_roofline_terms_math():
    t = RooflineTerms(flops=197e12, hbm_bytes=819e9 * 2,
                      coll_bytes=50e9 * 3, coll_by_kind={},
                      model_flops=197e12 * 256 * 0.5, chips=256)
    assert t.t_compute == pytest.approx(1.0)
    assert t.t_memory == pytest.approx(2.0)
    assert t.t_collective == pytest.approx(3.0)
    assert t.bottleneck == "collective"
    assert t.t_bound == pytest.approx(3.0)
    assert t.mfu_bound == pytest.approx(0.5 / 3.0)


def test_model_flops_dense_vs_moe():
    dense = get_arch("qwen1.5-4b").full
    moe = get_arch("qwen3-moe-30b-a3b").full
    tr = SHAPES["train_4k"]
    f_dense = model_flops_for(dense, tr)
    assert f_dense == pytest.approx(
        6 * dense.param_count() * 256 * 4096, rel=1e-6)
    # MoE: active params only (top-8 of 128 experts)
    f_moe = model_flops_for(moe, tr)
    assert f_moe < 6 * moe.param_count() * 256 * 4096 * 0.35
    # decode counts one token per sequence, inference 2*N*D
    dec = SHAPES["decode_32k"]
    assert model_flops_for(dense, dec) == pytest.approx(
        2 * dense.param_count() * 128, rel=1e-6)


def test_collectives_parsed_from_sharded_program():
    """An explicitly psum'd shard_map program yields all-reduce bytes."""
    import os
    # single device: use a 1-axis mesh (still emits a (trivial) all-reduce
    # in SPMD only with >1 devices, so just parse text for robustness)
    txt = """
HloModule test

ENTRY %main (p: f32[16,128]) -> f32[16,128] {
  %p = f32[16,128]{1,0} parameter(0)
  ROOT %ar = f32[16,128]{1,0} all-reduce(%p), to_apply=%add
}
"""
    res = analyze(txt)
    assert res["coll_by_kind"]["all-reduce"] == 16 * 128 * 4
    assert res["coll_bytes"] == 2 * 16 * 128 * 4   # ring 2x weighting
