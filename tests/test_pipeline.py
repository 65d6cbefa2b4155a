"""C2P2SL pod pipeline: numerical equivalence with the plain model.

Multi-device tests spawn a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count (never set globally —
smoke tests must see 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_interleaved_single_stage_matches_reference():
    """Fast in-process check of the interleaved tick loop: S=1 needs no
    extra devices, but v>1 still exercises the full interleaved schedule
    (sigma spacing, per-tick chunk gather, chunk-chain carry) plus the
    masked-row padding path (batch 6, k 4)."""
    import jax
    import jax.numpy as jnp
    from repro.data import lm_batch_for
    from repro.models import LM, LMConfig
    from repro.parallel.compat import make_mesh, mesh_context
    from repro.parallel.pipeline import PipelineSpec, make_pipelined_loss

    cfg = LMConfig(name="t", num_layers=4, d_model=32, n_heads=4, n_kv=2,
                   d_ff=64, vocab=128, dtype="float32")
    m = LM(cfg)
    p = m.init(jax.random.key(0))
    batch = lm_batch_for(cfg, 6, 16)
    mesh = make_mesh((1,), ("pod",))
    loss_ref, _ = m.forward(p, batch)
    g_ref = jax.grad(lambda p: m.forward(p, batch)[0])(p)
    for v in (1, 2, 4):
        spec = PipelineSpec(num_stages=1, microbatches=4, virtual_stages=v)
        loss_fn = make_pipelined_loss(m, spec, mesh=mesh)
        with mesh_context(mesh):
            loss_pipe, _ = jax.jit(loss_fn)(p, batch)
            g_pipe = jax.jit(jax.grad(lambda p: loss_fn(p, batch)[0]))(p)
        assert abs(float(loss_ref) - float(loss_pipe)) < 1e-5, f"v={v}"
        d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         g_ref, g_pipe)
        assert max(jax.tree.leaves(d)) < 1e-5, f"v={v}"


def test_split_stages_round_robin_and_divisibility():
    import jax.numpy as jnp
    import numpy as np
    from repro.parallel.pipeline import _sigma, _split_stages

    blocks = {"w": jnp.arange(8)[:, None] * jnp.ones((8, 3))}
    staged = _split_stages(blocks, 2, 2)            # S=2, v=2 -> 4 chunks
    # chunk c = j*S + s holds layers [c*2, c*2+2): stage s, virtual j
    w = np.asarray(staged["w"])
    assert w.shape == (2, 2, 2, 3)
    assert w[0, 0, :, 0].tolist() == [0, 1]         # chunk 0
    assert w[1, 0, :, 0].tolist() == [2, 3]         # chunk 1
    assert w[0, 1, :, 0].tolist() == [4, 5]         # chunk 2
    assert w[1, 1, :, 0].tolist() == [6, 7]         # chunk 3
    with pytest.raises(ValueError, match="not divisible"):
        _split_stages(blocks, 3, 2)
    # sigma: v=1 is the identity schedule; groups of S spaced S*v apart
    assert [_sigma(m, 2, 1) for m in range(4)] == [0, 1, 2, 3]
    assert [_sigma(m, 2, 2) for m in range(6)] == [0, 1, 4, 5, 8, 9]


@pytest.mark.slow
def test_pipeline_matches_plain_model():
    out = run_sub("""
        import jax, json
        import jax.numpy as jnp
        from repro.models import LM, LMConfig
        from repro.data import lm_batch_for
        from repro.parallel.compat import make_mesh, mesh_context
        from repro.parallel.pipeline import PipelineSpec, make_pipelined_loss

        cfg = LMConfig(name='t', num_layers=4, d_model=64, n_heads=4, n_kv=2,
                       d_ff=128, vocab=256, dtype='float32')
        m = LM(cfg)
        p = m.init(jax.random.key(0))
        batch = lm_batch_for(cfg, 8, 32)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        loss_ref, _ = m.forward(p, batch)
        g_ref = jax.grad(lambda p: m.forward(p, batch)[0])(p)
        spec = PipelineSpec(num_stages=2, microbatches=4)
        loss_fn = make_pipelined_loss(m, spec, mesh=mesh)
        with mesh_context(mesh):
            loss_pipe, _ = jax.jit(loss_fn)(p, batch)
            g_pipe = jax.jit(jax.grad(lambda p: loss_fn(p, batch)[0]))(p)
        d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         g_ref, g_pipe)
        print(json.dumps({
            "loss_ref": float(loss_ref), "loss_pipe": float(loss_pipe),
            "gdiff": max(jax.tree.leaves(d))}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert abs(res["loss_ref"] - res["loss_pipe"]) < 1e-5
    assert res["gdiff"] < 1e-5


@pytest.mark.slow
def test_pipeline_four_stages():
    """S=4 stages x k=8 micro-batches on an 8-device pod axis."""
    out = run_sub("""
        import jax, json
        import jax.numpy as jnp
        from repro.models import LM, LMConfig
        from repro.data import lm_batch_for
        from repro.parallel.compat import make_mesh, mesh_context
        from repro.parallel.pipeline import PipelineSpec, make_pipelined_loss

        cfg = LMConfig(name='t', num_layers=8, d_model=32, n_heads=4, n_kv=2,
                       d_ff=64, vocab=128, dtype='float32')
        m = LM(cfg)
        p = m.init(jax.random.key(1))
        batch = lm_batch_for(cfg, 8, 16)
        mesh = make_mesh((4, 2, 1), ("pod", "data", "model"))
        loss_ref, _ = m.forward(p, batch)
        spec = PipelineSpec(num_stages=4, microbatches=8)
        loss_fn = make_pipelined_loss(m, spec, mesh=mesh)
        with mesh_context(mesh):
            loss_pipe, _ = jax.jit(loss_fn)(p, batch)
        print(json.dumps({"ref": float(loss_ref), "pipe": float(loss_pipe)}))
    """, devices=8)
    res = json.loads(out.strip().splitlines()[-1])
    assert abs(res["ref"] - res["pipe"]) < 1e-5


@pytest.mark.slow
@pytest.mark.parametrize("k", [4, 5])
def test_interleaved_pipeline_matches_v1_and_reference(k):
    """virtual_stages=2 gradients == the v=1 pipeline == the unpipelined
    model, for divisible (k=4) and ragged (k=5, batch 10) micro-batch
    counts, on whichever lowering the installed JAX selects."""
    out = run_sub(f"""
        import jax, json
        import jax.numpy as jnp
        from repro.models import LM, LMConfig
        from repro.data import lm_batch_for
        from repro.parallel.compat import make_mesh, mesh_context
        from repro.parallel.pipeline import PipelineSpec, make_pipelined_loss

        cfg = LMConfig(name='t', num_layers=8, d_model=32, n_heads=4, n_kv=2,
                       d_ff=64, vocab=128, dtype='float32')
        m = LM(cfg)
        p = m.init(jax.random.key(1))
        batch = lm_batch_for(cfg, 10, 16)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        loss_ref, _ = m.forward(p, batch)
        g_ref = jax.grad(lambda p: m.forward(p, batch)[0])(p)
        grads = {{}}
        losses = {{}}
        for v in (1, 2):
            spec = PipelineSpec(num_stages=2, microbatches={k},
                                virtual_stages=v)
            loss_fn = make_pipelined_loss(m, spec, mesh=mesh)
            with mesh_context(mesh):
                loss_pipe, _ = jax.jit(loss_fn)(p, batch)
                grads[v] = jax.jit(
                    jax.grad(lambda p: loss_fn(p, batch)[0]))(p)
            losses[v] = float(loss_pipe)
        dmax = lambda a, b: max(jax.tree.leaves(jax.tree.map(
            lambda x, y: float(jnp.max(jnp.abs(x - y))), a, b)))
        print(json.dumps({{
            "loss_ref": float(loss_ref), "loss_v1": losses[1],
            "loss_v2": losses[2],
            "gdiff_v2_ref": dmax(grads[2], g_ref),
            "gdiff_v2_v1": dmax(grads[2], grads[1])}}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert abs(res["loss_ref"] - res["loss_v2"]) < 1e-5
    assert abs(res["loss_v1"] - res["loss_v2"]) < 1e-5
    assert res["gdiff_v2_ref"] < 1e-5
    assert res["gdiff_v2_v1"] < 1e-5


@pytest.mark.slow
def test_interleaved_four_stages_v2():
    """S=4 x v=2 (8 model chunks over 8 layers) on a 4-wide pod axis."""
    out = run_sub("""
        import jax, json
        import jax.numpy as jnp
        from repro.models import LM, LMConfig
        from repro.data import lm_batch_for
        from repro.parallel.compat import make_mesh, mesh_context
        from repro.parallel.pipeline import PipelineSpec, make_pipelined_loss

        cfg = LMConfig(name='t', num_layers=8, d_model=32, n_heads=4, n_kv=2,
                       d_ff=64, vocab=128, dtype='float32')
        m = LM(cfg)
        p = m.init(jax.random.key(1))
        batch = lm_batch_for(cfg, 8, 16)
        mesh = make_mesh((4, 2, 1), ("pod", "data", "model"))
        loss_ref, _ = m.forward(p, batch)
        g_ref = jax.grad(lambda p: m.forward(p, batch)[0])(p)
        spec = PipelineSpec(num_stages=4, microbatches=8, virtual_stages=2)
        loss_fn = make_pipelined_loss(m, spec, mesh=mesh)
        with mesh_context(mesh):
            loss_pipe, _ = jax.jit(loss_fn)(p, batch)
            g_pipe = jax.jit(jax.grad(lambda p: loss_fn(p, batch)[0]))(p)
        d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         g_ref, g_pipe)
        print(json.dumps({"ref": float(loss_ref), "pipe": float(loss_pipe),
                          "gdiff": max(jax.tree.leaves(d))}))
    """, devices=8)
    res = json.loads(out.strip().splitlines()[-1])
    assert abs(res["ref"] - res["pipe"]) < 1e-5
    assert res["gdiff"] < 1e-5


@pytest.mark.slow
def test_planner_chosen_plan_matches_reference():
    """Grad equivalence for an AUTO-picked plan: (S, k, v) comes from the
    checked-in roofline fixture via the auto-planner (the path train.py
    --pipeline-k auto --virtual-stages auto takes), not from hand flags —
    guarding the planner-to-pipeline plumbing the way the tests above
    guard hand-picked plans.  The fixture's interior optimum is a plan no
    hand-tuner would pick (k=13, v=2: ragged, interleaved)."""
    import json as _json

    from repro.analysis.autotune import plan_inputs_from_record
    from repro.parallel.pipeline import PipelineSpec

    fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                           "roofline_smoke.json")
    with open(fixture) as f:
        record = _json.load(f)
    spec, plan = PipelineSpec.auto_plan(plan_inputs_from_record(record))
    assert spec.num_stages == 2 and spec.virtual_stages > 1
    assert spec.microbatches not in (1, 2, 4, 8, 16)   # not a hand pick
    out = run_sub(f"""
        import jax, json
        import jax.numpy as jnp
        from repro.models import LM, LMConfig
        from repro.data import lm_batch_for
        from repro.parallel.compat import make_mesh, mesh_context
        from repro.parallel.pipeline import PipelineSpec, make_pipelined_loss

        cfg = LMConfig(name='t', num_layers=8, d_model=32, n_heads=4, n_kv=2,
                       d_ff=64, vocab=128, dtype='float32')
        m = LM(cfg)
        p = m.init(jax.random.key(1))
        batch = lm_batch_for(cfg, 26, 16)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        loss_ref, _ = m.forward(p, batch)
        g_ref = jax.grad(lambda p: m.forward(p, batch)[0])(p)
        spec = PipelineSpec(num_stages={spec.num_stages},
                            microbatches={spec.microbatches},
                            virtual_stages={spec.virtual_stages})
        loss_fn = make_pipelined_loss(m, spec, mesh=mesh)
        with mesh_context(mesh):
            loss_pipe, _ = jax.jit(loss_fn)(p, batch)
            g_pipe = jax.jit(jax.grad(lambda p: loss_fn(p, batch)[0]))(p)
        d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         g_ref, g_pipe)
        print(json.dumps({{"loss_ref": float(loss_ref),
                           "loss_pipe": float(loss_pipe),
                           "gdiff": max(jax.tree.leaves(d))}}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert abs(res["loss_ref"] - res["loss_pipe"]) < 1e-6
    # float32: the pipeline sums each weight gradient over 13 micro-batches
    # (plus the padded row) where the reference reduces the whole batch in
    # one pass.  The orders differ, so an O(1) gradient entry may move by a
    # few ulps (eps = 1.19e-7); 1e-6 is about eight ulps.
    assert res["gdiff"] < 1e-6


@pytest.mark.slow
def test_data_parallel_grads_match_single_device():
    """GSPMD DP run == single-device run for the same global batch."""
    out = run_sub("""
        import jax, json
        import jax.numpy as jnp
        from repro.models import LM, LMConfig
        from repro.data import lm_batch_for
        from repro.parallel.compat import make_mesh
        from repro.parallel.context import ParallelCtx, use_ctx
        from repro.parallel.sharding import ShardingPolicy

        cfg = LMConfig(name='t', num_layers=2, d_model=32, n_heads=4, n_kv=2,
                       d_ff=64, vocab=128, dtype='float32')
        m = LM(cfg)
        p = m.init(jax.random.key(0))
        batch = lm_batch_for(cfg, 8, 16)
        loss1 = float(m.forward(p, batch)[0])
        mesh = make_mesh((4, 2), ("data", "model"))
        policy = ShardingPolicy(mesh)
        psh = policy.param_shardings(p)
        bsh = policy.batch_shardings(batch)
        p_s = jax.device_put(p, psh)
        b_s = jax.device_put(batch, bsh)
        with use_ctx(ParallelCtx(mesh=mesh)):
            lossN = float(jax.jit(lambda p, b: m.forward(p, b)[0])(p_s, b_s))
        print(json.dumps({"l1": loss1, "lN": lossN}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert abs(res["l1"] - res["lN"]) < 2e-4


@pytest.mark.slow
def test_moe_sharded_matches_local():
    """The shard_map MoE dispatch == the single-device local path."""
    out = run_sub("""
        import jax, json
        import jax.numpy as jnp
        from repro.models import LM, LMConfig
        from repro.data import lm_batch_for
        from repro.parallel.compat import make_mesh, mesh_context
        from repro.parallel.context import ParallelCtx, use_ctx
        from repro.parallel.sharding import ShardingPolicy

        cfg = LMConfig(name='t', num_layers=2, d_model=32, n_heads=4, n_kv=2,
                       d_ff=32, vocab=128, moe_experts=4, moe_topk=2,
                       dtype='float32')
        m = LM(cfg)
        p = m.init(jax.random.key(0))
        batch = lm_batch_for(cfg, 8, 16)
        loss1 = float(m.forward(p, batch)[0])
        mesh = make_mesh((2, 4), ("data", "model"))
        with use_ctx(ParallelCtx(mesh=mesh)):
            with mesh_context(mesh):
                lossN = float(jax.jit(lambda p, b: m.forward(p, b)[0])(p, batch))
        print(json.dumps({"l1": loss1, "lN": lossN}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    # capacity buckets differ between 1-shard and 8-shard dispatch; the
    # (rare) dropped-token difference bounds the deviation
    assert abs(res["l1"] - res["lN"]) < 5e-3


@pytest.mark.slow
def test_moe_global_aux_sharded_matches_local_aux():
    """moe_global_aux=True: the data-sharded dispatch psums the router
    statistics, so the sharded AUX equals the single-device full-batch
    aux exactly (per-shard capacity drops only perturb outputs, never the
    pre-capacity statistics); with the flag off the per-shard aux mean
    deviates — the ROADMAP gap, quantified here on a real mesh."""
    out = run_sub("""
        import jax, json
        import jax.numpy as jnp
        from repro.models import LM, LMConfig
        from repro.data import lm_batch_for
        from repro.models.blocks import apply_block
        from repro.parallel.compat import make_mesh, mesh_context
        from repro.parallel.context import ParallelCtx, use_ctx
        from repro.models.moe import apply_moe

        cfg = LMConfig(name='t', num_layers=2, d_model=32, n_heads=4, n_kv=2,
                       d_ff=32, vocab=128, moe_experts=8, moe_topk=2,
                       dtype='float32')
        m = LM(cfg)
        p = m.init(jax.random.key(0))
        moe_p = jax.tree.map(lambda a: a[0], p["blocks"])["moe"]
        x = jax.random.normal(jax.random.key(1), (8, 16, 32), jnp.float32)
        kw = dict(topk=2, cap_factor=4.0, act=cfg.act)
        _, aux_local = apply_moe(moe_p, x, **kw)
        mesh = make_mesh((4, 2), ("data", "model"))
        with use_ctx(ParallelCtx(mesh=mesh)):
            with mesh_context(mesh):
                _, aux_off = jax.jit(
                    lambda x: apply_moe(moe_p, x, **kw))(x)
                _, aux_on = jax.jit(
                    lambda x: apply_moe(moe_p, x, global_aux=True, **kw))(x)
        print(json.dumps({"local": float(aux_local),
                          "sharded_off": float(aux_off),
                          "sharded_on": float(aux_on)}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["sharded_on"] == pytest.approx(res["local"], rel=1e-5)
    gap_off = abs(res["sharded_off"] - res["local"])
    gap_on = abs(res["sharded_on"] - res["local"])
    assert gap_off > 1e-4          # the documented deviation is real...
    assert gap_on < gap_off / 10   # ...and the psum'd aux removes it
