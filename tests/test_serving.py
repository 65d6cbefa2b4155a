"""Continuous-batching serving engine + split-inference tests.

The load-bearing guarantee: continuous batching changes WHEN work runs
— requests join and leave the slot arena at arbitrary steps, slots are
reused, prefill is chunked — but never WHAT it computes.  Every
request's emitted tokens must equal its solo batch=1 run-to-completion
decode bit-for-bit, greedy and sampled.  The split-inference half pins
the same property across a real loopback socket plus the wire-honesty
contract (measured INFER payload bytes == planner billing within 1%).
"""
import asyncio
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import LM, LMConfig
from repro.runtime.qos import ServingQoS, percentile
from repro.serving import kv
from repro.serving.engine import (ServingEngine, convoy_units,
                                  make_sample_step, solo_decode)
from repro.serving.scheduler import Request, Scheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = LMConfig(name="serve-test", num_layers=2, d_model=32, n_heads=2,
               n_kv=1, d_ff=32, vocab=64, dtype="float32")
# one stack per decode-state family: a global KV cache (CFG), a local
# ring buffer shorter than the requests, and rwkv recurrent states
FAMILIES = {
    "attn": CFG,
    "local": LMConfig(name="serve-local", num_layers=2, d_model=32,
                      n_heads=2, n_kv=1, d_ff=32, vocab=64,
                      pattern=("local",) * 2, window=4, dtype="float32"),
    "rwkv": LMConfig(name="serve-rwkv", family="ssm", num_layers=2,
                     d_model=32, n_heads=2, n_kv=2, d_ff=32, vocab=64,
                     pattern=("rwkv",) * 2, rwkv_head_dim=16, rwkv_lora=8,
                     norm="layernorm", dtype="float32"),
    "moe": LMConfig(name="serve-moe", family="moe", num_layers=2,
                    d_model=32, n_heads=2, n_kv=1, d_ff=32, vocab=64,
                    moe_experts=2, moe_topk=1, moe_capacity=1.0,
                    dtype="float32"),
}
# the MoE stack is served on 16 slots: 16 tokens over 2 experts outgrow
# an expert capacity of 8, so a lane's token would be dropped if its
# dispatch counted the other lanes' tokens
SLOTS = {"moe": 16}


@pytest.fixture(scope="module")
def model_params():
    model = LM(CFG)
    return model, model.init(jax.random.key(0))


def _reqs(specs):
    rng = np.random.default_rng(11)
    return [Request(rid=i, prompt=rng.integers(0, CFG.vocab, plen),
                    max_new_tokens=gen)
            for i, (plen, gen) in enumerate(specs)]


# ---------------------------------------------------------------------------
# Engine bit-identity: staggered join/leave, slot reuse, chunked prefill.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_staggered_requests_bitexact_vs_solo(family):
    """Requests submitted mid-flight, ragged gens forcing slot churn on
    a 2-slot arena: every output equals the solo batch=1 decode, for a
    global KV cache, a local ring buffer (the requests outrun its
    window) and rwkv states, each lane at its own position; and for a
    MoE stack on a full 16-slot arena, whose lanes' tokens share the
    experts."""
    model = LM(FAMILIES[family])
    params = model.init(jax.random.key(0))
    slots = SLOTS.get(family, 2)
    # 5 requests for 2 slots, 20 for 16
    reqs = _reqs([(3, 4), (5, 2), (3, 6), (4, 3), (5, 5)]
                 * max(1, slots // 4))
    eng = ServingEngine(model, params, slots=slots, cache_len=16)
    for r in reqs[:slots]:
        assert eng.submit(r)
    for _ in range(3):                       # r1 (gen 2) frees its slot
        eng.step_once()
    for r in reqs[slots:]:
        assert eng.submit(r)
    out = eng.run()
    assert set(out) == {r.rid for r in reqs}
    for r in reqs:
        ref = solo_decode(model, params, r.prompt, r.max_new_tokens,
                          cache_len=16)
        np.testing.assert_array_equal(out[r.rid], ref)
    stats = eng.stats()
    assert stats["qos"]["completed"] == len(reqs)
    # more tenants than slots -> slots were reused
    assert stats["decode_steps"] * slots >= sum(r.max_new_tokens
                                                for r in reqs)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_decode_step_position_vector_equals_scalar(family):
    """A [B] vector of equal positions is the scalar call, bit for bit;
    with distinct positions each lane equals the scalar call at its own
    position, logits and cache."""
    model = LM(FAMILIES[family])
    params = model.init(jax.random.key(1))
    prompts = jnp.asarray(
        np.random.default_rng(4).integers(0, 64, (3, 6)), jnp.int32)
    _, state = jax.jit(model.prefill_with_cache,
                       static_argnames=("cache_len", "cache_dtype"))(
        params, {"tokens": prompts}, cache_len=8, cache_dtype=jnp.float32)
    tok = prompts[:, -1:]
    decode = jax.jit(model.decode_step)
    axes = kv.slot_axes(model, 8)

    def lane(out, i):
        logits, cache = out
        return [logits[i]] + jax.tree.leaves(kv.take_slot(cache, axes, i))

    def same(a, b):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    scalar = {p: decode(params, tok, state["cache"], jnp.int32(p))
              for p in (4, 5, 6)}
    equal = decode(params, tok, state["cache"], jnp.full((3,), 6, jnp.int32))
    same(jax.tree.leaves(equal), jax.tree.leaves(scalar[6]))
    ragged = decode(params, tok, state["cache"],
                    jnp.asarray([6, 4, 5], jnp.int32))
    for i, p in enumerate((6, 4, 5)):
        same(lane(ragged, i), lane(scalar[p], i))


def test_arena_programs_run_in_place(model_params):
    """Every decode step and every row write consumes the arena it is
    given (the donated buffers are written, not copied):
    ``arena_inplace`` counts decode steps plus admitted rows."""
    model, params = model_params
    reqs = _reqs([(3, 4), (5, 2), (3, 6), (4, 3)])
    eng = ServingEngine(model, params, slots=2, cache_len=16)
    eng.submit(reqs[0])
    eng.step_once()
    held = jax.tree.leaves(eng.cache)
    assert not any(a.is_deleted() for a in held)
    eng.step_once()
    assert all(a.is_deleted() for a in held)
    eng.run(reqs[1:])
    stats = eng.stats()
    assert stats["qos"]["admitted"] == len(reqs)
    assert stats["arena_inplace"] == stats["decode_steps"] + len(reqs)


def test_sampled_bitexact_and_slot_independent(model_params):
    """Temperature sampling inside the jitted step uses per-request
    fold_in keys: outputs equal the solo chain AND are invariant to the
    arena size / slot assignment."""
    model, params = model_params
    reqs = _reqs([(4, 5), (4, 3), (4, 6), (4, 4)])
    outs = {}
    for slots in (1, 3):
        eng = ServingEngine(model, params, slots=slots, cache_len=16,
                            temperature=0.7, seed=9)
        outs[slots] = eng.run(_reqs([(4, 5), (4, 3), (4, 6), (4, 4)]))
    for r in reqs:
        ref = solo_decode(model, params, r.prompt, r.max_new_tokens,
                          cache_len=16, temperature=0.7, seed=9,
                          rid=r.rid)
        np.testing.assert_array_equal(outs[1][r.rid], ref)
        np.testing.assert_array_equal(outs[3][r.rid], ref)


def test_prefill_chunk_budget_equivalence(model_params):
    """A tight prefill-chunk token budget splits admissions across many
    engine iterations; outputs are identical to an unconstrained run."""
    model, params = model_params
    specs = [(6, 3)] * 5
    outs = {}
    for budget in (6, 512):                  # 1 prompt/chunk vs all 5
        eng = ServingEngine(model, params, slots=5, cache_len=16,
                            prefill_chunk_tokens=budget)
        outs[budget] = eng.run(_reqs(specs))
    assert outs[6].keys() == outs[512].keys()
    for rid in outs[6]:
        np.testing.assert_array_equal(outs[6][rid], outs[512][rid])
    # and the constrained run really did chunk
    eng2 = ServingEngine(model, params, slots=5, cache_len=16,
                         prefill_chunk_tokens=6)
    eng2.run(_reqs(specs))
    assert eng2.prefill_chunks == 5


def test_engine_rejects_and_counts(model_params):
    model, params = model_params
    eng = ServingEngine(model, params, slots=2, cache_len=8, max_queue=2)
    ok = eng.submit(Request(rid=0, prompt=np.zeros(6, np.int32),
                            max_new_tokens=4))     # 6 + 4 > 8
    assert not ok
    assert eng.submit(Request(rid=1, prompt=np.zeros(2, np.int32),
                              max_new_tokens=2))
    assert eng.submit(Request(rid=2, prompt=np.zeros(2, np.int32),
                              max_new_tokens=2))
    assert not eng.submit(Request(rid=3, prompt=np.zeros(2, np.int32),
                                  max_new_tokens=2))   # queue full
    snap = eng.qos.snapshot()
    assert snap["rejected"] == 2 and snap["admitted"] == 0


# ---------------------------------------------------------------------------
# Fused decode+sample step (the static serve path).
# ---------------------------------------------------------------------------


def test_make_sample_step_greedy_matches_unfused(model_params):
    from repro.parallel.steps import make_decode_step
    model, params = model_params
    prompts = jnp.asarray(
        np.random.default_rng(2).integers(0, CFG.vocab, (2, 4)), jnp.int32)
    logits, ss = jax.jit(
        model.prefill_with_cache,
        static_argnames=("cache_len", "cache_dtype"))(
            params, {"tokens": prompts}, cache_len=10,
            cache_dtype=jnp.float32)
    _, ss_ref = jax.jit(
        model.prefill_with_cache,
        static_argnames=("cache_len", "cache_dtype"))(
            params, {"tokens": prompts}, cache_len=10,
            cache_dtype=jnp.float32)
    decode = jax.jit(make_decode_step(model))
    step = make_sample_step(model, 0.0)
    key = jax.random.key(0)
    tok = jnp.argmax(logits, -1, keepdims=True).astype(jnp.int32)
    tok_ref = tok
    for _ in range(4):
        tok, lg, ss, key = step(params, ss, tok, key)
        lg_ref, ss_ref = decode(params, ss_ref, tok_ref)
        tok_ref = jnp.argmax(lg_ref, -1, keepdims=True).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(tok_ref))
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(lg_ref))


def test_serve_cli_sampled_matches_old_host_chain():
    """serve.main --temperature now samples INSIDE the jit; the carried
    key splits in the same order as the old host loop, so the emitted
    tokens are unchanged."""
    from repro.configs import get_arch
    from repro.launch import serve
    from repro.parallel.steps import make_decode_step

    gen, batch, plen, seed, temp = 4, 2, 3, 5, 0.8
    toks = serve.main(["--arch", "qwen1.5-4b", "--batch", str(batch),
                       "--prompt-len", str(plen), "--gen", str(gen),
                       "--seed", str(seed), "--temperature", str(temp)])
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
    key = jax.random.key(seed)
    tok = jnp.argmax(logits, -1, keepdims=True).astype(jnp.int32)
    ref = []
    for _ in range(gen):
        logits, ss = decode(params, ss, tok)
        key, sub = jax.random.split(key)
        tok = jax.random.categorical(
            sub, logits / temp, axis=-1)[:, None].astype(jnp.int32)
        ref.append(np.asarray(tok[:, 0]))
    np.testing.assert_array_equal(toks, np.stack(ref, axis=1))


# ---------------------------------------------------------------------------
# Slot arena + scheduler + freelist units.
# ---------------------------------------------------------------------------


def test_slot_axes_take_put_roundtrip(model_params):
    model, _ = model_params
    axes = kv.slot_axes(model, 8)
    cache = model.init_cache(3, 8, jnp.float32)
    cache = jax.tree.map(
        lambda a: jnp.arange(a.size, dtype=a.dtype).reshape(a.shape),
        cache)
    row = kv.take_slot(cache, axes, 1)
    back = kv.put_slot(cache, axes, row, 1)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a row written into another slot reads back from there
    moved = kv.take_slot(kv.put_slot(cache, axes, row, 2), axes, 2)
    for a, b in zip(jax.tree.leaves(row), jax.tree.leaves(moved)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_freelist_lifo_and_double_free():
    fl = kv.FreeList(3)
    assert [fl.alloc(), fl.alloc()] == [0, 1]
    fl.free(0)
    assert fl.alloc() == 0                   # LIFO: immediate reuse
    fl.free(0)
    with pytest.raises(ValueError):
        fl.free(0)                           # double free
    with pytest.raises(ValueError):
        fl.free(7)                           # out of range


def test_scheduler_buckets_policy_and_rejects():
    s = Scheduler(cache_len=32, prefill_chunk_tokens=8,
                  policy="longest_first")
    assert not s.submit(Request(rid=0, prompt=np.zeros(30, np.int32),
                                max_new_tokens=4))       # cache overflow
    for rid, (plen, gen) in enumerate([(4, 2), (4, 9), (6, 5), (4, 9)],
                                      start=1):
        assert s.submit(Request(rid=rid, prompt=np.zeros(plen, np.int32),
                                max_new_tokens=gen))
    # LPT: head is rid=2 (gen 9, plen 4); same-length rid=4 joins; the
    # 8-token budget stops after those two; rid=3 (plen 6) is skipped
    chunk = s.next_chunk(free_slots=4)
    assert [r.rid for r in chunk] == [2, 4]
    chunk = s.next_chunk(free_slots=4)
    assert [r.rid for r in chunk] == [3]     # next-longest gen bucket
    assert [r.rid for r in s.next_chunk(4)] == [1]
    assert s.next_chunk(4) == [] and s.rejected == 1
    # head always admitted even over budget
    s2 = Scheduler(cache_len=64, prefill_chunk_tokens=4)
    s2.submit(Request(rid=0, prompt=np.zeros(10, np.int32),
                      max_new_tokens=1))
    assert [r.rid for r in s2.next_chunk(2)] == [0]
    with pytest.raises(ValueError):
        Scheduler(cache_len=8, policy="shortest_first")


def test_convoy_units():
    reqs = _reqs([(4, 8), (4, 2), (4, 2), (4, 2)])
    # batch 2: groups (8,2) and (2,2) -> 16 + 4*4 + 2*2*2
    assert convoy_units(reqs, 2) == 16 + 2 * 8 + 2 * 2


# ---------------------------------------------------------------------------
# ServingQoS latency percentiles (scripted clock).
# ---------------------------------------------------------------------------


def test_percentile_nearest_rank():
    xs = [10.0, 20.0, 30.0, 40.0]
    assert percentile(xs, 50) == 20.0
    assert percentile(xs, 99) == 40.0
    assert percentile(xs, 0) == 10.0
    assert percentile([], 50) is None
    with pytest.raises(ValueError):
        percentile(xs, 150)


def test_serving_qos_scripted_clock():
    t = {"now": 0.0}
    q = ServingQoS(clock=lambda: t["now"])
    for rid, (wait, ttft, per_tok, n) in enumerate([(0.5, 1.0, 0.5, 3),
                                                    (1.5, 2.0, 0.25, 5),
                                                    (0.25, 4.0, 1.0, 2)]):
        t["now"] = 0.0
        q.record_submit(rid)
        t["now"] = wait
        q.record_admit(rid, step=0)
        t["now"] = ttft
        q.record_token(rid, step=1)
        for i in range(1, n):
            t["now"] = ttft + i * per_tok
            q.record_token(rid, step=1 + i)
        q.record_done(rid, step=n)
    q.record_submit(99)                      # queued, never admitted
    q.record_submit(98)
    q.record_reject(98)
    snap = q.snapshot()
    assert snap["admitted"] == 3 and snap["completed"] == 3
    assert snap["rejected"] == 1 and snap["queued"] == 1
    assert snap["tokens_emitted"] == 10
    lat = snap["latency"]
    assert lat["p50_ttft_s"] == 2.0 and lat["p99_ttft_s"] == 4.0
    assert lat["p50_tok_s"] == 0.5 and lat["p99_tok_s"] == 1.0
    assert lat["p50_queue_s"] == 0.5 and lat["p99_queue_s"] == 1.5
    with pytest.raises(ValueError):
        q.record_submit(99)                  # duplicate submit
    with pytest.raises(KeyError):
        q.record_token(1234, step=0)


# ---------------------------------------------------------------------------
# Split inference: composition bit-identity + INFER wire honesty.
# ---------------------------------------------------------------------------


def test_split_decode_composition_bitexact(model_params):
    from repro.serving.infer import SplitDecode
    model, params = model_params
    prompts = jnp.asarray(
        np.random.default_rng(3).integers(0, CFG.vocab, (2, 5)), jnp.int32)
    split = SplitDecode(model, 1)
    ue_p, bs_p = split.split_params(params)
    # jitted on both sides, as the serving path runs them: eager op-by-op
    # dispatch rounds some fused sums differently from a compiled program
    prefill = jax.jit(lambda f, p, x: f(p, x, cache_len=12),
                      static_argnums=0)
    acts, ue_c = prefill(split.ue_prefill, ue_p, prompts)
    logits, bs_c = prefill(split.bs_prefill, bs_p, acts)
    ue_decode, bs_decode = jax.jit(split.ue_decode), jax.jit(split.bs_decode)
    decode = jax.jit(model.decode_step)
    ml, ms = jax.jit(
        model.prefill_with_cache,
        static_argnames=("cache_len", "cache_dtype"))(
            params, {"tokens": prompts}, cache_len=12,
            cache_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ml))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    cache = ms["cache"]
    for pos in range(5, 8):
        a, ue_c = ue_decode(ue_p, tok, ue_c, jnp.asarray(pos, jnp.int32))
        lg, bs_c = bs_decode(bs_p, a, bs_c, jnp.asarray(pos, jnp.int32))
        mlg, cache = decode(params, tok, cache, jnp.asarray(pos, jnp.int32))
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(mlg))
        tok = jnp.argmax(lg, -1).astype(jnp.int32)[:, None]


def test_split_decode_validation(model_params):
    from repro.serving.infer import SplitDecode, _require_dense
    model, _ = model_params
    with pytest.raises(ValueError):
        SplitDecode(model, 0)
    with pytest.raises(ValueError):
        SplitDecode(model, CFG.num_layers)
    with pytest.raises(ValueError):
        _require_dense("int8+topk0.25")      # INFER hop is forward-only
    assert _require_dense("fp8") == "fp8"


@pytest.mark.parametrize("wire", ["none", "int8", "fp8"])
def test_infer_loopback_wire_honesty(model_params, wire):
    """The INFER hop over a REAL loopback socket: measured payload bytes
    match the planner's billed_hop_bytes within 1%; 'none' tokens are
    bit-identical to the monolithic greedy chain (quantized codecs are
    lossy by design — shape and completion only)."""
    import asyncio

    from repro.serving.infer import _run_split_infer
    model, params = model_params
    prompts = np.random.default_rng(5).integers(
        0, CFG.vocab, (2, 4)).astype(np.int32)
    gen = 3
    # a deadline of its own: a hang on the socket fails this test only
    res = asyncio.run(asyncio.wait_for(_run_split_infer(
        model, params, cut=1, prompts=prompts, gen=gen, cache_len=8,
        wire_dtype=wire), 240))
    assert res["tokens"].shape == (2, gen)
    rel = abs(res["measured_payload_bytes"] - res["billed_payload_bytes"]) \
        / res["billed_payload_bytes"]
    assert rel <= 0.01, (wire, res)
    # gen+1 uplink frames: 1 prefill + gen decode acts
    assert res["frames"] == gen + 1
    assert res["client_payload_bytes"] == res["measured_payload_bytes"]
    if wire == "none":
        ref = np.stack([solo_decode(model, params, prompts[i], gen,
                                    cache_len=8) for i in range(2)])
        np.testing.assert_array_equal(res["tokens"], ref)


# ---------------------------------------------------------------------------
# Serving planner objective (analysis/autotune).
# ---------------------------------------------------------------------------


def _serving_inputs(**kw):
    from repro.analysis.autotune import ServingInputs
    base = dict(decode_lane_s=1e-3, prefill_s_per_token=1e-3,
                arrival_hz=2.0, prompt_tokens=8.0, gen_tokens=32.0,
                step_overhead_s=5e-3)
    base.update(kw)
    return ServingInputs(**base)


def test_serving_wall_shape_and_overload():
    from repro.analysis.autotune import serving_wall
    inp = _serving_inputs()
    ev = serving_wall(inp, 8)
    assert ev["rho"] < 1 and np.isfinite(ev["p99_ttft_s"])
    assert ev["capacity_tokens_per_s"] > ev["tokens_per_s"] > 0
    # an undersized arena is overloaded -> infinite latency, not a raise
    over = serving_wall(_serving_inputs(arrival_hz=50.0), 1)
    assert over["p99_ttft_s"] == float("inf")
    # larger arenas pay more per step (fixed-shape computes every lane)
    assert serving_wall(inp, 32)["per_token_s"] \
        > serving_wall(inp, 4)["per_token_s"]
    with pytest.raises(ValueError):
        serving_wall(inp, 0)


def test_choose_serving_plan_interior_and_errors():
    from repro.analysis.autotune import choose_serving_plan, serving_wall
    inp = _serving_inputs()
    plan = choose_serving_plan(inp)
    assert plan.slots in inp.slot_candidates and plan.rho < 1
    # argmin property: no candidate beats the chosen p99
    for s in inp.slot_candidates:
        ev = serving_wall(inp, s)
        assert plan.p99_ttft_s <= ev["p99_ttft_s"] * (1 + 1e-8)
    with pytest.raises(ValueError):          # all overloaded
        choose_serving_plan(_serving_inputs(arrival_hz=1e6))
    with pytest.raises(ValueError):          # topk illegal on INFER hop
        choose_serving_plan(inp, wire_candidates=["int8+topk0.25"])


def test_serving_plan_split_hop_codec():
    """Split serving: a dense codec shrinks the INFER hop time, so at a
    tight link the coded plan strictly beats 'none'."""
    from repro.analysis.autotune import choose_serving_plan
    inp = _serving_inputs(d_model=256, act_bytes=4.0,
                          link_bw_Bps=2e6, hop_overhead_s=1e-4)
    plan = choose_serving_plan(inp, wire_candidates=["none", "int8",
                                                     "fp8"])
    assert plan.wire_dtype == "int8"
    none_plan = choose_serving_plan(inp.with_wire("none"))
    assert plan.p99_ttft_s < none_plan.p99_ttft_s


def test_plan_args_serve_flavor():
    import argparse

    from repro.launch.plan_args import add_plan_args
    ap = argparse.ArgumentParser()
    add_plan_args(ap, flavor="serve")
    args = ap.parse_args(["--wire-dtype", "int8",
                          "--plan-out", "plan.json"])
    assert args.wire_dtype == "int8" and args.plan_out == "plan.json"
    assert not hasattr(args, "pipeline_k")   # train-only flags absent
    with pytest.raises(ValueError):
        add_plan_args(argparse.ArgumentParser(), flavor="infer")


# ---------------------------------------------------------------------------
# Bench baseline sync (the CI diff-gate guarantee, in tier-1).
# ---------------------------------------------------------------------------


def test_committed_bench_baseline_matches_serve_bench():
    """benchmarks/BENCH_pipeline.json must stay in sync with the live
    serving engine — a cost-model or scheduler change cannot land
    without regenerating the baseline."""
    sys.path.insert(0, ROOT)
    try:
        from benchmarks.run import diff_rows
        from benchmarks.serve_bench import main as bench_main
    finally:
        sys.path.remove(ROOT)
    with open(os.path.join(ROOT, "benchmarks",
                           "BENCH_pipeline.json")) as f:
        base = json.load(f)
    result = json.loads(json.dumps(
        bench_main(quick=True),
        default=lambda o: o.tolist() if hasattr(o, "tolist") else str(o)))
    fails = diff_rows(base["rows"],
                      [{"name": "serve_bench", "result": result}])
    assert fails == [], fails
    assert result["modeled_speedup"] >= 1.5
    assert result["tokens_bitexact_vs_solo"]
    assert result["infer_wire_ok"]
