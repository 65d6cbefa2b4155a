"""Per-architecture smoke tests: reduced same-family config, one forward /
train step on CPU, output shapes + finite values (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch
from repro.data import lm_batch_for
from repro.models import LM
from repro.parallel.steps import (init_serve_state, make_decode_step,
                                  make_lm_train_step)
from repro.training import adamw

ARCH_NAMES = sorted(ARCHS)


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_forward_and_train_step(name):
    spec = get_arch(name)
    cfg = spec.smoke
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    batch = lm_batch_for(cfg, 4, 16, seed=1)

    loss, mets = jax.jit(model.forward)(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{name}: non-finite loss"

    opt = adamw(1e-3)
    state = {"params": params, "opt_state": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    step = jax.jit(make_lm_train_step(model, opt, microbatches=2))
    state, mets = step(state, batch)
    assert int(state["step"]) == 1
    for leaf in jax.tree.leaves(state["params"]):
        assert bool(jnp.all(jnp.isfinite(leaf))), f"{name}: NaN params"
    assert bool(jnp.isfinite(mets["loss"]))


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_decode_step(name):
    spec = get_arch(name)
    cfg = spec.smoke
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    serve = init_serve_state(model, 2, 8, cache_dtype=jnp.float32)
    if cfg.enc_layers:
        frames = jnp.zeros((2, cfg.enc_seq, cfg.d_model), jnp.float32)
        enc_out = model._encode(params, frames)
        serve["cache"] = model.fill_cross_kv(params, enc_out, serve["cache"])
    decode = jax.jit(make_decode_step(model))
    tok = jnp.zeros((2, 1), jnp.int32)
    for _ in range(3):
        logits, serve = decode(params, serve, tok)
        tok = jnp.argmax(logits, -1, keepdims=True).astype(jnp.int32)
    assert logits.shape == (2, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits))), f"{name}: NaN logits"
    assert int(serve["position"]) == 3


@pytest.mark.parametrize("name", ["qwen1.5-4b", "rwkv6-3b",
                                  "recurrentgemma-2b", "starcoder2-3b"])
def test_decode_matches_forward(name):
    """Teacher-forced decode must reproduce the training-forward logits —
    the KV-cache / recurrent-state bookkeeping is exactly consistent."""
    cfg = get_arch(name).smoke
    model = LM(cfg)
    params = model.init(jax.random.key(3))
    rng = np.random.default_rng(0)
    seq = 12
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (2, seq)), jnp.int32)

    h = model.hidden(params, {"tokens": tokens})
    from repro.models.common import apply_norm  # final logits by hand
    dt = h.dtype
    logits_fwd = (h[:, -1] @ model._head_w(params, dt))[:, :cfg.vocab]

    serve = init_serve_state(model, 2, seq + 1, cache_dtype=jnp.float32)
    decode = jax.jit(make_decode_step(model))
    logits = None
    for t in range(seq):
        logits, serve = decode(params, serve, tokens[:, t:t + 1])
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(logits_fwd, np.float32),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("name", ["qwen1.5-4b", "rwkv6-3b",
                                  "recurrentgemma-2b", "whisper-small"])
def test_chunked_prefill_matches_token_loop(name):
    """prefill_with_cache (one forward pass filling the cache) == feeding
    the prompt through decode_step token by token — including G continued
    decode steps from both states."""
    cfg = get_arch(name).smoke
    model = LM(cfg)
    params = model.init(jax.random.key(3))
    from repro.data import lm_batch_for
    S, G = 10, 4
    batch = lm_batch_for(cfg, 2, S + G, seed=7)
    prompt = {k: (v[:, :S] if k in ("tokens", "labels") else v)
              for k, v in batch.items() if k != "labels"}
    cache_len = S + G

    logits_a, serve_a = model.prefill_with_cache(
        params, prompt, cache_len, cache_dtype=jnp.float32)

    serve_b = init_serve_state(model, 2, cache_len, cache_dtype=jnp.float32)
    if cfg.family == "audio":
        enc_out = model._encode(params,
                                jnp.asarray(prompt["frames"], jnp.float32))
        serve_b["cache"] = model.fill_cross_kv(params, enc_out,
                                               serve_b["cache"])
    decode = jax.jit(make_decode_step(model))
    toks = jnp.asarray(prompt["tokens"], jnp.int32)
    logits_b = None
    for t in range(S):
        logits_b, serve_b = decode(params, serve_b, toks[:, t:t + 1])
    np.testing.assert_allclose(np.asarray(logits_a), np.asarray(logits_b),
                               rtol=3e-3, atol=3e-3)
    la, lb = logits_a, logits_b
    for _ in range(G):
        tok = jnp.argmax(la, -1, keepdims=True).astype(jnp.int32)
        la, serve_a = decode(params, serve_a, tok)
        lb, serve_b = decode(params, serve_b, tok)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                               rtol=3e-3, atol=3e-3)


def test_whisper_cross_kv_cache_equivalence():
    """Prefill-cached cross-attention K/V == per-step recompute
    (the whisper decode optimization, EXPERIMENTS.md §Perf bonus)."""
    cfg = get_arch("whisper-small").smoke
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    frames = jnp.asarray(rng.standard_normal((2, cfg.enc_seq, cfg.d_model)),
                         jnp.float32)
    enc_out = model._encode(params, frames)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 5)), jnp.int32)

    serve = init_serve_state(model, 2, 8, cache_dtype=jnp.float32)
    serve["cache"] = model.fill_cross_kv(params, enc_out, serve["cache"])
    decode = jax.jit(make_decode_step(model))
    la = None
    for t in range(5):
        la, serve = decode(params, serve, toks[:, t:t + 1])

    cache_b = model.init_cache(2, 8, jnp.float32)
    cache_b = {k: v for k, v in cache_b.items() if k not in ("ck", "cv")}
    lb = None
    for t in range(5):
        lb, cache_b = model.decode_step(params, toks[:, t:t + 1], cache_b,
                                        t, enc_out=enc_out)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                               rtol=2e-4, atol=2e-4)


def test_full_configs_match_assignment():
    """The exact published numbers from the assignment table."""
    expect = {
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256_000),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151_936),
        "command-r-plus-104b": (64, 12288, 96, 8, 33792, 256_000),
        "starcoder2-3b": (30, 3072, 24, 2, 12288, 49_152),
        "codeqwen1.5-7b": (32, 4096, 32, 32, 13440, 92_416),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49_155),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151_936),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257_216),
        "rwkv6-3b": (32, 2560, 40, 40, 8960, 65_536),
        "whisper-small": (12, 768, 12, 12, 3072, 51_865),
    }
    for name, (L, d, h, kv, f, v) in expect.items():
        cfg = get_arch(name).full
        assert (cfg.num_layers, cfg.d_model, cfg.n_heads, cfg.n_kv,
                cfg.d_ff, cfg.vocab) == (L, d, h, kv, f, v), name
    # MoE expert counts / top-k
    assert get_arch("granite-moe-3b-a800m").full.moe_experts == 40
    assert get_arch("granite-moe-3b-a800m").full.moe_topk == 8
    assert get_arch("qwen3-moe-30b-a3b").full.moe_experts == 128
    assert get_arch("qwen3-moe-30b-a3b").full.moe_topk == 8


def test_chip_config_keeps_published_widths():
    """``chip`` is FULL cut only in the keys its module lists in
    ``REDUCED`` (depth, vocabulary slice); every width is published."""
    import dataclasses

    from repro.configs import qwen15_4b
    spec = get_arch("qwen1.5-4b")
    full = dataclasses.asdict(spec.full)
    chip = dataclasses.asdict(spec.config("chip"))
    changed = {k for k in full if full[k] != chip[k]} - {"name"}
    assert changed == set(qwen15_4b.REDUCED)
    for key, (published, held, why) in qwen15_4b.REDUCED.items():
        assert (full[key], chip[key]) == (published, held) and why


def test_size_chip_without_chip_config_is_an_error():
    """``--size chip`` on an architecture with no chip share stops; it
    never falls back to another size."""
    from repro.launch import serve, train
    assert get_arch("rwkv6-3b").chip is None
    with pytest.raises(ValueError, match="no chip-share config"):
        get_arch("rwkv6-3b").config("chip")
    for main in (train.main, serve.main):
        with pytest.raises(SystemExit, match="--size chip"):
            main(["--arch", "rwkv6-3b", "--size", "chip"])


def test_shape_skips_documented():
    """8 long_500k cells skip with a reason; ssm/hybrid run it."""
    skips = [a for a in ARCH_NAMES
             if get_arch(a).skip_reason("long_500k") is not None]
    runs = [a for a in ARCH_NAMES
            if get_arch(a).skip_reason("long_500k") is None]
    assert sorted(runs) == ["recurrentgemma-2b", "rwkv6-3b"]
    assert len(skips) == 8
    for a in skips:
        assert len(get_arch(a).skip_reason("long_500k")) > 10


def test_resnet_paper_model():
    params = resnet_init = None
    from repro.models import resnet
    params = resnet.init_resnet18(jax.random.key(0))
    x = jnp.zeros((2, 32, 32, 3), jnp.float32)
    logits = resnet.forward(params, x)
    assert logits.shape == (2, 10)
    loss, mets = resnet.loss_fn(params, {"images": x,
                                         "labels": jnp.zeros((2,), jnp.int32)})
    assert bool(jnp.isfinite(loss))


def test_moe_global_aux_recovers_full_batch_statistics():
    """ROADMAP item, quantified: the mean of per-shard auxes (the
    documented per-micro-batch/per-shard deviation) differs from the
    full-batch aux, while averaging the router STATISTICS first (what
    apply_moe(global_aux=True) psums across shards) recovers it exactly
    for equal shard sizes."""
    from repro.models.moe import _moe_local, router_aux

    rng = np.random.default_rng(0)
    d, e, topk, t = 16, 8, 2, 64
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, e)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((e, d, 32)) * 0.1, jnp.float32)
    w3 = jnp.asarray(rng.standard_normal((e, d, 32)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((e, 32, d)) * 0.1, jnp.float32)
    kw = dict(topk=topk, capacity=64, act="silu")

    _, aux_full, me_full, ce_full = _moe_local(
        x, router, w1, w3, w2, return_stats=True, **kw)

    shards = [x[: t // 2], x[t // 2:]]
    stats = [_moe_local(s, router, w1, w3, w2, return_stats=True, **kw)
             for s in shards]
    aux_mean = float(sum(s[1] for s in stats) / 2)          # per-shard aux
    me_g = sum(s[2] for s in stats) / 2                     # pmean'd stats
    ce_g = sum(s[3] for s in stats) / 2
    aux_global = float(router_aux(me_g, ce_g))

    assert aux_global == pytest.approx(float(aux_full), rel=1e-6)
    gap = abs(aux_mean - float(aux_full))
    assert gap > 1e-4, "deviation should be measurable on random routing"
    # the deviation the flag removes is real but bounded
    assert gap < 0.5 * float(aux_full)


def test_moe_global_aux_flag_noop_without_mesh():
    """Without a mesh the local aux already sees every token: the config
    flag must not change the loss."""
    from repro.models import LMConfig

    cfg = dict(name="t", num_layers=2, d_model=32, n_heads=4, n_kv=2,
               d_ff=32, vocab=128, moe_experts=4, moe_topk=2,
               dtype="float32")
    m1 = LM(LMConfig(**cfg))
    m2 = LM(LMConfig(moe_global_aux=True, **cfg))
    p = m1.init(jax.random.key(0))
    batch = lm_batch_for(m1.cfg, 4, 16)
    l1 = float(m1.forward(p, batch)[0])
    l2 = float(m2.forward(p, batch)[0])
    assert l1 == l2


def test_serve_emits_exactly_gen_tokens():
    """Regression for the serve decode-loop off-by-one: the old loop
    appended the PRE-decode token each iteration, so the output held the
    prefill argmax + the first gen-1 decodes and the final decode's
    sampled token was computed then silently discarded.  The emitted
    sequence must be exactly the --gen decode outputs, matching a
    hand-rolled greedy chain."""
    from repro.launch import serve

    gen, batch, plen, seed = 5, 2, 4, 3
    toks = serve.main(["--arch", "qwen1.5-4b", "--batch", str(batch),
                       "--prompt-len", str(plen), "--gen", str(gen),
                       "--seed", str(seed)])
    assert toks.shape == (batch, gen)

    cfg = get_arch("qwen1.5-4b").smoke
    model = LM(cfg)
    params = model.init(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (batch, plen)),
                          jnp.int32)
    logits, ss = jax.jit(
        model.prefill_with_cache,
        static_argnames=("cache_len", "cache_dtype"))(
            params, {"tokens": prompts}, cache_len=plen + gen,
            cache_dtype=jnp.float32)
    decode = jax.jit(make_decode_step(model))
    tok = jnp.argmax(logits, -1, keepdims=True).astype(jnp.int32)
    ref = []
    for _ in range(gen):
        logits, ss = decode(params, ss, tok)
        tok = jnp.argmax(logits, -1, keepdims=True).astype(jnp.int32)
        ref.append(np.asarray(tok[:, 0]))
    np.testing.assert_array_equal(toks, np.stack(ref, axis=1))
