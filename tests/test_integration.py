"""End-to-end integration: train -> checkpoint -> elastic restart;
compressed-gradient training; Lemma-1 pipeline-k bridge."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ao import pipeline_k_auto
from repro.data import TokenTaskConfig, token_batches
from repro.models import LM, LMConfig
from repro.parallel.steps import make_lm_train_step
from repro.training import adamw, checkpoint
from repro.training.compress import init_error_fb

CFG = LMConfig(name="itest", num_layers=2, d_model=64, n_heads=4, n_kv=2,
               d_ff=128, vocab=256, dtype="float32")


def make_state(model, opt, compress=False):
    params = model.init(jax.random.key(0))
    state = {"params": params, "opt_state": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    if compress:
        state["error_fb"] = init_error_fb(params)
    return state


def test_train_checkpoint_restart_bitexact(tmp_path):
    """Crash/restart at step 6 reproduces the uninterrupted run exactly."""
    model = LM(CFG)
    opt = adamw(1e-3)
    step = jax.jit(make_lm_train_step(model, opt))
    data = lambda: token_batches(TokenTaskConfig(vocab=CFG.vocab), 8, 16,
                                 seed=3)

    # uninterrupted 10 steps
    st = make_state(model, opt)
    it = data()
    for _ in range(10):
        st, _ = step(st, next(it))

    # interrupted: 6 steps, checkpoint, "crash", restore, 4 more
    st2 = make_state(model, opt)
    it = data()
    for _ in range(6):
        st2, _ = step(st2, next(it))
    checkpoint.save(str(tmp_path), 6, st2)
    restored = checkpoint.restore(str(tmp_path), 6, make_state(model, opt))
    assert int(restored["step"]) == 6
    for _ in range(4):
        restored, _ = step(restored, next(it))

    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                     st["params"], restored["params"])
    assert max(jax.tree.leaves(d)) == 0.0


def test_elastic_restore_after_shrink(tmp_path):
    """Checkpoint taken on one layout restores onto another target tree
    (the pod-loss shrink flow: fault.plan_rescale + re-shard restore)."""
    from repro.training.fault import plan_rescale
    model = LM(CFG)
    opt = adamw(1e-3)
    st = make_state(model, opt)
    checkpoint.save(str(tmp_path), 1, st)
    new_shape = plan_rescale({"pod": 2, "data": 2, "model": 2}, 1)
    assert new_shape["pod"] == 1
    # restore into a freshly-initialized (differently-seeded) state tree:
    # values must come from the checkpoint, not the init
    fresh = make_state(model, opt)
    fresh["params"] = jax.tree.map(lambda x: x + 1.0, fresh["params"])
    restored = checkpoint.restore(str(tmp_path), 1, fresh)
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                     restored["params"], st["params"])
    assert max(jax.tree.leaves(d)) == 0.0


def test_compressed_training_converges():
    """int8+EF compressed grads still reduce the loss (EPSL generalized)."""
    model = LM(CFG)
    opt = adamw(3e-3)
    step = jax.jit(make_lm_train_step(model, opt, compress=True))
    st = make_state(model, opt, compress=True)
    it = token_batches(TokenTaskConfig(vocab=CFG.vocab), 8, 16, seed=5)
    first = last = None
    for i in range(30):
        st, mets = step(st, next(it))
        if first is None:
            first = float(mets["loss"])
        last = float(mets["loss"])
    assert last < first - 0.1
    assert "error_fb" in st
    # error feedback carry is alive and bounded
    efb_max = max(float(jnp.max(jnp.abs(e)))
                  for e in jax.tree.leaves(st["error_fb"]))
    assert 0.0 < efb_max < 1.0


def test_pipeline_k_auto_lemma1():
    # compute-rich regime: k capped only by granularity
    assert pipeline_k_auto(10.0, 1.0, k_cap=16) == 16
    # comm-bound: eta = 0.5 -> k = floor(1/(1-0.5)) = 2
    assert pipeline_k_auto(1.0, 2.0, k_cap=16) == 2
    # eta -> 1 from below: k grows (1/(1-0.9) = 10)
    assert pipeline_k_auto(0.9, 1.0, k_cap=64) == 10
    # degenerate link
    assert pipeline_k_auto(1.0, 0.0, k_cap=8) == 8


def test_train_launcher_compress_grads_flag(tmp_path):
    """--compress-grads is a real launcher flag (the compress.py docstring
    used to promise it without wiring): two steps run, the state carries
    the error-feedback tree, and the loss is finite."""
    from repro.launch.train import main as train_main

    metrics = tmp_path / "m.json"
    history = train_main([
        "--arch", "qwen1.5-4b", "--size", "smoke", "--steps", "2",
        "--batch", "4", "--seq", "16", "--log-every", "1",
        "--compress-grads", "--metrics-out", str(metrics)])
    assert len(history) == 2
    assert np.isfinite(history[-1]["loss"])


def test_compress_grads_resumes_from_pre_flag_checkpoint(tmp_path):
    """Turning on --compress-grads must not brick resume: checkpoints
    saved without the flag carry no error_fb tree — the launcher
    restores everything else and restarts EF at zero."""
    from repro.launch.train import main as train_main

    ckpt = str(tmp_path / "ck")
    args = ["--arch", "qwen1.5-4b", "--size", "smoke", "--batch", "4",
            "--seq", "16", "--log-every", "1", "--ckpt-dir", ckpt,
            "--ckpt-every", "1"]
    train_main(args + ["--steps", "1"])                     # no flag
    history = train_main(args + ["--steps", "2", "--compress-grads"])
    assert len(history) == 1                                # resumed at 1
    assert history[-1]["step"] == 2
    assert np.isfinite(history[-1]["loss"])


@pytest.mark.parametrize("from_env", [False, True],
                         ids=["unset", "set"])
def test_compile_cache_dir_rule(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself and the helper
    sets nothing.  Unset: the cache is the checkout's fixed .jax_cache/,
    the same path on every call (the path is part of the cache key)."""
    import os

    from repro.launch import cache
    before = jax.config.jax_compilation_cache_dir
    untouched = str(tmp_path / "untouched")
    jax.config.update("jax_compilation_cache_dir", untouched)
    try:
        if from_env:
            monkeypatch.setenv(cache.ENV, str(tmp_path / "env"))
            assert cache.use_compile_cache() == str(tmp_path / "env")
            assert jax.config.jax_compilation_cache_dir == untouched
        else:
            monkeypatch.delenv(cache.ENV, raising=False)
            root = os.path.dirname(os.path.dirname(os.path.abspath(
                __file__)))
            want = os.path.join(root, ".jax_cache")
            assert cache.use_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
            assert cache.use_compile_cache() == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
