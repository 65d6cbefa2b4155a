"""The blocked causal-attention kernel (``kernels/causal_attention.py``)
against the jnp path it replaces, and the choice between them
(``models.common.attention_path``).

The kernel runs in interpret mode here.  Model-level cases take it the
way a TPU does: ``common._on_tpu`` is steered to True, so
``attention_path`` picks the kernel wherever the shape allows.  In float32 both paths
compute the same sums in another order, so they agree to rounding; in
bfloat16 each is held to its distance from the float32 model."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import lm_batch_for
from repro.models import LM, LMConfig, common
from repro.models import blocks
from repro.parallel.steps import make_lm_train_step
from repro.serving.engine import solo_decode
from repro.training.optim import sgd

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")

#: qwen-shaped: RMSNorm, QKV bias, gated SiLU MLP, head width 128
MHA = LMConfig(name="t", num_layers=2, d_model=256, n_heads=2, n_kv=2,
               d_ff=512, vocab=256, qkv_bias=True, dtype="float32")
GQA = dataclasses.replace(MHA, n_heads=4, n_kv=2, head_dim=128)
SEQ = 256


@pytest.fixture
def on_tpu(monkeypatch):
    """The path is chosen as on a TPU; the kernel still interprets."""
    monkeypatch.setattr(common, "_on_tpu", lambda: True)


def _rel(a, b) -> float:
    a, b = (np.asarray(x, np.float64) for x in (a, b))
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _train_step(cfg, batch):
    """One step of ``make_lm_train_step`` (2 micro-batches) under SGD at
    lr 1 and no momentum: (loss, the step's change of every parameter,
    which is minus its gradient), and the attention paths it traced."""
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    opt = sgd(lr=1.0, momentum=0.0)
    state = {"params": params, "opt_state": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    with common.AttentionPaths() as paths:
        new, mets = jax.jit(make_lm_train_step(model, opt,
                                               microbatches=2))(state, batch)
    change = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                          new["params"], params)
    return float(mets["loss"]), change, dict(paths)


@pytest.mark.parametrize("cfg", [MHA, GQA], ids=["mha", "gqa"])
def test_train_step_kernel_matches_xla_path(monkeypatch, cfg):
    batch = lm_batch_for(cfg, 4, SEQ)
    loss_x, d_x, paths_x = _train_step(cfg, batch)
    monkeypatch.setattr(common, "_on_tpu", lambda: True)
    loss_k, d_k, paths_k = _train_step(cfg, batch)
    assert set(paths_x) == {"xla: backend cpu"}
    assert set(paths_k) == {"kernel"}
    assert abs(loss_k - loss_x) < 1e-5 * abs(loss_x)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(d_k),
                            jax.tree.leaves(d_x)):
        assert _rel(a, b) < 1e-4, jax.tree_util.keystr(path)


def test_train_step_kernel_in_bf16_as_close_as_xla_path(monkeypatch):
    """In bfloat16 the kernel's step is as close to the float32 model's
    as the jnp path's: its MXU operands are bf16 like every other
    matmul's, and its scores and statistics f32."""
    cfg = dataclasses.replace(MHA, dtype="bfloat16")
    batch = lm_batch_for(cfg, 4, SEQ)
    loss_ref, d_ref, _ = _train_step(MHA, batch)
    loss_x, d_x, _ = _train_step(cfg, batch)
    monkeypatch.setattr(common, "_on_tpu", lambda: True)
    loss_k, d_k, paths = _train_step(cfg, batch)
    assert set(paths) == {"kernel"}
    # a bf16 ulp at the loss (~5.6) is 2^-6: stay within a quarter of one
    assert abs(loss_k - loss_ref) < 2.0 ** -8
    for (path, k), x, r in zip(jax.tree_util.tree_leaves_with_path(d_k),
                               jax.tree.leaves(d_x), jax.tree.leaves(d_ref)):
        assert _rel(k, r) < 1.5 * _rel(x, r) + 1e-3, jax.tree_util.keystr(path)


def test_pipeline_kernel_matches_xla_path():
    """Two stages on the host mesh: the kernel traces and lowers inside
    the pipeline's ``shard_map`` (Manual over ``pod``, varying types
    checked), and its gradients match the jnp path's; a cast of the
    kernel's outputs transposed into a sum over the stages would double
    them."""
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.data import lm_batch_for
        from repro.models import LM, LMConfig, common
        from repro.parallel.compat import make_mesh, mesh_context
        from repro.parallel.pipeline import PipelineSpec, make_pipelined_loss
        cfg = LMConfig(name="t", num_layers=2, d_model=256, n_heads=2,
                       n_kv=2, d_ff=512, vocab=256, qkv_bias=True,
                       dtype="float32")
        m = LM(cfg)
        p = m.init(jax.random.key(0))
        batch = lm_batch_for(cfg, 4, 256)
        mesh = make_mesh((2,), ("pod",))
        loss_fn = make_pipelined_loss(
            m, PipelineSpec(num_stages=2, microbatches=2), mesh=mesh)
        out = {}
        for on_tpu in (False, True):
            common._on_tpu = lambda: on_tpu
            vg = jax.value_and_grad(lambda p: loss_fn(p, batch)[0])
            with common.AttentionPaths() as paths, mesh_context(mesh):
                loss, g = jax.jit(vg)(p)
            out[str(on_tpu)] = {"loss": float(loss), "paths": dict(paths),
                                "g": [np.asarray(a).tolist()
                                      for a in jax.tree.leaves(g)]}
        print(json.dumps(out))
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=SRC)
    run = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    out = json.loads(run.stdout.strip().splitlines()[-1])
    xla, kern = out["False"], out["True"]
    assert set(xla["paths"]) == {"xla: backend cpu"}
    assert set(kern["paths"]) == {"kernel"}
    assert abs(kern["loss"] - xla["loss"]) < 1e-5 * abs(xla["loss"])
    for i, (a, b) in enumerate(zip(kern["g"], xla["g"])):
        assert _rel(a, b) < 1e-4, i


def test_prefill_kernel_matches_xla_path_and_solo_decode(monkeypatch):
    """``prefill_with_cache`` through the kernel gives the jnp path's
    last logits and caches, so ``solo_decode`` (kernel prefill, then
    decode steps, which never take it) emits the same first tokens."""
    params = LM(MHA).init(jax.random.key(1))
    prompt = np.asarray(jax.random.randint(jax.random.key(2), (SEQ,), 0,
                                           MHA.vocab))
    batch = {"tokens": jnp.asarray(prompt)[None]}

    def prefill():
        model = LM(MHA)       # a model of its own: no program traced before
        with common.AttentionPaths() as paths:
            logits, state = jax.jit(lambda p, b: model.prefill_with_cache(
                p, b, cache_len=SEQ + 8, cache_dtype=jnp.float32))(
                    params, batch)
            toks = solo_decode(model, params, prompt, 4, cache_len=SEQ + 8)
        return logits, state["cache"], toks, dict(paths)

    logits_x, cache_x, toks_x, paths_x = prefill()
    monkeypatch.setattr(common, "_on_tpu", lambda: True)
    logits_k, cache_k, toks_k, paths_k = prefill()
    assert set(paths_x) == {"xla: backend cpu"}
    assert set(paths_k) == {"kernel"}
    assert _rel(logits_k, logits_x) < 1e-5
    for a, b in zip(jax.tree.leaves(cache_k), jax.tree.leaves(cache_x)):
        assert _rel(a, b) < 1e-5
    np.testing.assert_array_equal(toks_k, toks_x)


@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "tpu"])
def test_engine_counts_its_attention_paths(monkeypatch, tpu):
    """``ServingEngine.stats()`` keeps the paths its programs traced:
    each prefill bucket's attention (the kernel where a TPU would take
    it); the decode step never reaches ``chunked_attention``."""
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import Request

    monkeypatch.setattr(common, "_on_tpu", lambda: tpu)
    model = LM(MHA)
    params = model.init(jax.random.key(3))
    eng = ServingEngine(model, params, slots=2, cache_len=SEQ + 8)
    r = np.random.default_rng(4)
    eng.run([Request(i, r.integers(0, MHA.vocab, n), 2)
             for i, n in enumerate((128, 256))])
    stats = eng.stats()
    assert stats["decode_steps"] > 0
    want = "kernel" if tpu else "xla: backend cpu"
    assert stats["attention_paths"] == {want: 2}    # one per bucket


@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "tpu"])
def test_nested_attention_counters_each_count(monkeypatch, tpu):
    """A counter entered around ``ServingEngine.run`` and the engine's own
    both count its prefill buckets, though they hold equal counts; once
    both are left, a later trace reaches neither."""
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import Request

    monkeypatch.setattr(common, "_on_tpu", lambda: tpu)
    model = LM(MHA)
    params = model.init(jax.random.key(3))
    eng = ServingEngine(model, params, slots=2, cache_len=SEQ + 8)
    r = np.random.default_rng(4)
    with common.AttentionPaths() as outer:
        eng.run([Request(i, r.integers(0, MHA.vocab, n), 2)
                 for i, n in enumerate((128, 256))])
    want = {"kernel" if tpu else "xla: backend cpu": 2}
    assert outer == want and eng.stats()["attention_paths"] == want
    assert common._RECORDING == []
    solo_decode(model, params, r.integers(0, MHA.vocab, 128), 2,
                cache_len=SEQ + 8)
    assert outer == want and eng.stats()["attention_paths"] == want


# ----------------------------------------------------------------- dispatch

def _paths_of_block(cfg, kind, *, seq=SEQ, prefix_len=0, enc=False):
    x = jax.ShapeDtypeStruct((1, seq, cfg.d_model), jnp.float32)
    p = jax.eval_shape(lambda: blocks.init_block(jax.random.key(0), cfg,
                                                 kind))
    enc_out = jax.ShapeDtypeStruct((1, 64, cfg.d_model), jnp.float32) \
        if enc else None
    with common.AttentionPaths() as paths:
        jax.eval_shape(lambda p, x, e: blocks.apply_block(
            p, x, cfg, kind, positions=jnp.arange(seq),
            prefix_len=prefix_len, enc_out=e), p, x, enc_out)
    return dict(paths)


@pytest.mark.parametrize("case,want", [
    (dict(kind="attn"), {"kernel": 1}),
    (dict(kind="attn", seq=384), {"kernel": 1}),
    (dict(kind="local"), {"xla: window": 1}),
    (dict(kind="attn", prefix_len=16), {"xla: prefix": 1}),
    (dict(kind="enc"), {"xla: not causal": 1}),
    (dict(kind="xattn", enc=True), {"kernel": 1, "xla: cross": 1}),
    (dict(kind="attn", seq=200), {"xla: seq 200 % 128": 1}),
    (dict(kind="attn", head_dim=64), {"xla: head_dim 64 % 128": 1}),
], ids=["causal", "causal-384", "window", "prefix", "enc", "xattn",
        "seq200", "dh64"])
def test_attention_path_by_shape(on_tpu, case, want):
    case = dict(case)
    cfg = dataclasses.replace(MHA, window=64,
                              head_dim=case.pop("head_dim", 128))
    assert _paths_of_block(cfg, **case) == want


@pytest.mark.parametrize("kind", ["attn", "local", "enc", "xattn"])
def test_attention_path_on_cpu_is_xla(kind):
    """Off the TPU the kernel never takes a call."""
    cfg = dataclasses.replace(MHA, window=64)
    paths = _paths_of_block(cfg, kind, enc=(kind == "xattn"))
    assert set(paths) == {"xla: backend cpu"}


def test_kernel_matches_reference_attention(on_tpu):
    """``chunked_attention`` on the kernel path alone, GQA, bf16 in and out:
    its output within a bf16 rounding of float32 softmax attention."""
    r = np.random.default_rng(0)
    q = jnp.asarray(r.standard_normal((2, SEQ, 4, 128)), jnp.bfloat16)
    k, v = (jnp.asarray(r.standard_normal((2, SEQ, 2, 128)), jnp.bfloat16)
            for _ in range(2))
    pos = jnp.arange(SEQ)
    with common.AttentionPaths() as paths:
        out = common.chunked_attention(q, k, v, q_positions=pos,
                                       kv_positions=pos, causal=True)
    assert paths == {"kernel": 1}
    assert out.dtype == jnp.bfloat16 and out.shape == q.shape
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    kf, vf = (jnp.repeat(a, 2, axis=2) for a in (kf, vf))
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / np.sqrt(128)
    logits = jnp.where(jnp.tril(jnp.ones((SEQ, SEQ), bool)), logits, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), vf)
    assert _rel(out.astype(jnp.float32), ref) < 2.0 ** -8


# ---------------------------------------------------------- positions contract

def test_every_caller_passes_arange_positions(monkeypatch):
    """The kernel masks by index, so every caller of ``apply_block`` and
    ``apply_block_prefill`` must pass ``positions = arange(S)``: the LM's
    forward and prefill, the pipeline's stages, the split trainer's two
    halves and split inference's two halves."""
    from repro.parallel.compat import make_mesh, mesh_context
    from repro.parallel.pipeline import PipelineSpec, make_pipelined_loss
    from repro.serving.infer import SplitDecode
    from repro.sl.split import lm_split

    seen = []
    orig = blocks.chunked_attention

    def spy(q, k, v, *, q_positions, kv_positions, **kw):
        if kv_positions is q_positions:
            jax.debug.callback(lambda p: seen.append(np.asarray(p)),
                               q_positions)
        return orig(q, k, v, q_positions=q_positions,
                    kv_positions=kv_positions, **kw)

    monkeypatch.setattr(blocks, "chunked_attention", spy)
    cfg = dataclasses.replace(MHA, num_layers=2)
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    batch = lm_batch_for(cfg, 2, 16)
    callers = {
        "forward": lambda: model.forward(params, batch),
        "prefill": lambda: model.prefill_with_cache(params, batch,
                                                    cache_len=24),
    }
    mesh = make_mesh((1,), ("pod",))
    loss_fn = make_pipelined_loss(
        model, PipelineSpec(num_stages=1, microbatches=2), mesh=mesh)

    def pipeline():
        with mesh_context(mesh):
            return jax.jit(loss_fn)(params, batch)

    callers["pipeline"] = pipeline
    sl = lm_split(model, 1)
    ue, bs = sl.split_params(params)

    def split():
        acts = sl.ue_fwd(ue, batch["tokens"])
        return sl.bs_loss(bs, acts, batch["labels"])

    callers["split"] = split
    sd = SplitDecode(model, 1)
    ue_p, bs_p = sd.split_params(params)

    def infer():
        acts, _ = sd.ue_prefill(ue_p, batch["tokens"], cache_len=24)
        return sd.bs_prefill(bs_p, acts, cache_len=24)

    callers["split inference"] = infer
    for name, call in callers.items():
        seen.clear()
        jax.block_until_ready(call())
        assert seen, name
        for p in seen:
            np.testing.assert_array_equal(p, np.arange(p.shape[-1]),
                                          err_msg=name)
