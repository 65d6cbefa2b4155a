"""Serving driver — thin CLI over ``repro.serving``.

Three modes, one flag surface:

* **static** (default): prefill a prompt batch, then run the fused
  decode+sample jit (``serving.engine.make_sample_step``) for ``--gen``
  steps — the original batched convoy path, kept as the baseline.
* ``--continuous``: the continuous-batching engine
  (``serving.engine.ServingEngine``) — a slot arena of ``--slots``
  lanes (or ``--slots auto``: ``analysis/autotune.choose_serving_plan``
  on measured step costs), chunked prefill interleaved with one jitted
  fixed-shape decode step, per-request QoS latency percentiles.
* ``--split-cut L``: split inference — the UE half (embed + blocks[:L])
  ships coded cut activations over a real loopback socket as INFER
  frames (``--wire-dtype`` none/int8/fp8) to the BS half, which samples
  and replies; prints the measured-vs-billed wire-honesty audit.

PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --size smoke \
    --batch 8 --prompt-len 32 --gen 32
PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b \
    --continuous --requests 24 --gen-mix 8,32,128 --slots 8
PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b \
    --split-cut 2 --wire-dtype int8
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SIZES, get_arch
from repro.launch.plan_args import add_plan_args
from repro.models.lm import LM
from repro.parallel.steps import init_serve_state, make_decode_step
from repro.serving.scheduler import POLICIES, Request


def prefill_into_cache(decode, params, tokens, serve_state):
    """Token-by-token prompt feed (reference path, any family).

    The production path is ``LM.prefill_with_cache`` — one full-sequence
    forward that fills the cache directly (equivalence proven in
    tests/test_models.py::test_chunked_prefill_matches_token_loop).
    """
    logits = None
    for t in range(tokens.shape[1]):
        logits, serve_state = decode(params, serve_state, tokens[:, t:t + 1])
    return logits, serve_state


def _static_serve(model, params, prompts, frames, args, cache_len):
    """Batched convoy serving: one prefill, ``--gen`` fused
    decode+sample steps.  Returns emitted tokens [batch, gen]."""
    from repro.serving.engine import make_sample_step

    t0 = time.perf_counter()
    if args.token_loop_prefill or model.cfg.family == "vlm":
        decode = jax.jit(make_decode_step(model))
        serve_state = init_serve_state(model, args.batch, cache_len,
                                       cache_dtype=jnp.float32)
        if frames is not None:
            enc_out = model._encode(params, frames)
            serve_state["cache"] = model.fill_cross_kv(
                params, enc_out, serve_state["cache"])
        logits, serve_state = prefill_into_cache(decode, params, prompts,
                                                 serve_state)
    else:
        prompt_batch = {"tokens": prompts}
        if frames is not None:
            prompt_batch["frames"] = frames
        logits, serve_state = jax.jit(
            model.prefill_with_cache,
            static_argnames=("cache_len", "cache_dtype"))(
                params, prompt_batch, cache_len=cache_len,
                cache_dtype=jnp.float32)
    t_prefill = time.perf_counter() - t0

    step = make_sample_step(model, args.temperature)
    key = jax.random.key(args.seed)
    out_tokens = []
    # The prefill logits' argmax seeds the first decode; each decode's
    # sampled output token is appended AFTER that decode runs, so all
    # ``--gen`` decode steps land in the output (the old loop appended
    # the pre-decode token and silently discarded the final decode's).
    tok = jnp.argmax(logits, axis=-1, keepdims=True).astype(jnp.int32)
    t0 = time.perf_counter()
    for _ in range(args.gen):
        tok, logits, serve_state, key = step(params, serve_state, tok, key)
        out_tokens.append(np.asarray(tok[:, 0]))
    jax.block_until_ready(logits)
    t_decode = time.perf_counter() - t0

    toks = np.stack(out_tokens, axis=1)
    print(f"prefill: {args.prompt_len} toks x {args.batch} seqs "
          f"in {t_prefill:.2f}s")
    print(f"decode:  {args.gen} toks x {args.batch} seqs in {t_decode:.2f}s "
          f"({args.gen * args.batch / max(t_decode, 1e-9):.1f} tok/s)")
    print("sample:", toks[0, :16].tolist())
    return toks


def request_mix(cfg, args) -> list:
    """Deterministic request set: ``--requests`` prompts of
    ``--prompt-len`` tokens, generation budgets cycled from ``--gen-mix``
    through a seeded shuffle (ragged on purpose — the convoy tax)."""
    rng = np.random.default_rng(args.seed)
    mix = [int(g) for g in str(args.gen_mix).split(",") if g]
    gens = np.asarray([mix[i % len(mix)] for i in range(args.requests)])
    rng.shuffle(gens)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, args.prompt_len),
                    max_new_tokens=int(gens[i]))
            for i in range(args.requests)]


def _measure_serving_inputs(model, params, args, cache_len):
    """Two-point measurement of the engine's step cost for ``--slots
    auto``: decode steps at two arena sizes give the per-lane slope and
    the fixed overhead; one prefill gives the per-token cost."""
    from repro.serving.engine import ServingEngine

    def step_s(slots):
        eng = ServingEngine(model, params, slots=slots,
                            cache_len=cache_len, seed=args.seed)
        for r in range(slots):
            eng.submit(Request(rid=r, prompt=np.zeros(1, np.int32),
                               max_new_tokens=cache_len - 1))
        eng.step_once()                      # admit + first (compile) step
        t0 = time.perf_counter()
        for _ in range(4):
            eng._decode_once()
        return (time.perf_counter() - t0) / 4

    t1, t4 = step_s(1), step_s(4)
    lane_s = max((t4 - t1) / 3, 1e-9)
    prompts = jnp.zeros((1, args.prompt_len), jnp.int32)
    pf = jax.jit(model.prefill_with_cache,
                 static_argnames=("cache_len", "cache_dtype"))
    jax.block_until_ready(pf(params, {"tokens": prompts},
                             cache_len=cache_len,
                             cache_dtype=jnp.float32)[0])
    t0 = time.perf_counter()
    jax.block_until_ready(pf(params, {"tokens": prompts},
                             cache_len=cache_len,
                             cache_dtype=jnp.float32)[0])
    prefill_tok_s = (time.perf_counter() - t0) / args.prompt_len
    from repro.analysis.autotune import ServingInputs
    return ServingInputs(
        decode_lane_s=lane_s, step_overhead_s=max(t1 - lane_s, 0.0),
        prefill_s_per_token=prefill_tok_s,
        arrival_hz=args.arrival_hz, prompt_tokens=float(args.prompt_len),
        gen_tokens=float(np.mean([int(g) for g in
                                  str(args.gen_mix).split(",") if g])),
        wire_dtype=args.wire_dtype, act_bytes=4.0,
        d_model=model.cfg.d_model)


def _resolve_slots(model, params, args, cache_len):
    """``--slots`` -> (slot count, ServingPlan evidence | None)."""
    if str(args.slots) != "auto":
        n = int(args.slots) or args.batch
        return n, None
    if not args.arrival_hz > 0:
        raise SystemExit("--slots auto needs --arrival-hz (the offered "
                         "load the serving planner optimizes for)")
    from repro.analysis.autotune import choose_serving_plan
    inp = _measure_serving_inputs(model, params, args, cache_len)
    plan = choose_serving_plan(inp)
    print(f"serving plan: slots={plan.slots} wire={plan.wire_dtype} "
          f"p99_ttft={plan.p99_ttft_s * 1e3:.2f} ms "
          f"({plan.tokens_per_s:.1f} tok/s, rho={plan.rho:.2f})")
    return plan.slots, plan


def _continuous_serve(model, params, args, cache_len):
    """Continuous batching: the slot-arena engine over a ragged request
    mix.  Returns ``{rid: emitted tokens}``."""
    from repro.serving.engine import ServingEngine, convoy_units

    slots, plan = _resolve_slots(model, params, args, cache_len)
    requests = request_mix(model.cfg, args)
    engine = ServingEngine(
        model, params, slots=slots, cache_len=cache_len,
        temperature=args.temperature, seed=args.seed,
        prefill_chunk_tokens=args.prefill_chunk_tokens,
        policy=args.policy)
    t0 = time.perf_counter()
    outputs = engine.run(requests)
    wall = time.perf_counter() - t0
    stats = engine.stats()
    emitted = sum(len(v) for v in outputs.values())
    convoy = convoy_units(requests, args.batch)
    print(f"continuous: {len(outputs)}/{len(requests)} requests, "
          f"{emitted} tokens in {wall:.2f}s "
          f"({emitted / max(wall, 1e-9):.1f} tok/s)")
    print(f"engine units {stats['engine_units']} vs convoy(batch="
          f"{args.batch}) {convoy} -> modeled speedup "
          f"{convoy / max(stats['engine_units'], 1):.2f}x; "
          f"occupancy {stats['occupancy_mean']:.2f}/{slots}")
    lat = stats["qos"]["latency"]
    if lat["p50_ttft_s"] is not None:
        print(f"latency: p50 ttft {lat['p50_ttft_s'] * 1e3:.1f} ms, "
              f"p99 ttft {lat['p99_ttft_s'] * 1e3:.1f} ms; queue wait "
              f"p50 {lat['p50_queue_s'] * 1e3:.1f} ms, "
              f"p99 {lat['p99_queue_s'] * 1e3:.1f} ms")
    if args.plan_out:
        doc = {"mode": "continuous", "slots": slots,
               "wire_dtype": args.wire_dtype,
               "plan": plan.to_dict() if plan is not None else None,
               "stats": stats}
        with open(args.plan_out, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {args.plan_out}")
    return outputs


def _split_serve(model, params, prompts, args, cache_len):
    """Split inference over the loopback socket; prints the wire-honesty
    audit.  Returns emitted tokens [batch, gen]."""
    from repro.serving.infer import run_split_infer

    res = run_split_infer(model, params, cut=args.split_cut,
                          prompts=np.asarray(prompts), gen=args.gen,
                          cache_len=cache_len,
                          wire_dtype=args.wire_dtype)
    rel = abs(res["measured_payload_bytes"] - res["billed_payload_bytes"]) \
        / max(res["billed_payload_bytes"], 1e-9)
    print(f"split-infer: cut={args.split_cut} wire={args.wire_dtype} "
          f"{res['frames']} INFER frames, measured "
          f"{res['measured_payload_bytes']} B vs billed "
          f"{res['billed_payload_bytes']:.0f} B (rel {rel:.2e})")
    print("sample:", res["tokens"][0, :16].tolist())
    if args.plan_out:
        doc = {"mode": "split", "cut": args.split_cut,
               "wire_dtype": args.wire_dtype,
               "measured_payload_bytes": res["measured_payload_bytes"],
               "billed_payload_bytes": res["billed_payload_bytes"],
               "frames": res["frames"]}
        with open(args.plan_out, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {args.plan_out}")
    return res["tokens"]


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--size", default="smoke", choices=SIZES)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--token-loop-prefill", action="store_true",
                    help="reference prefill path (token by token) instead "
                         "of the chunked one-pass prefill")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching engine instead of the "
                         "static convoy loop")
    ap.add_argument("--requests", type=int, default=24,
                    help="continuous mode: request count")
    ap.add_argument("--gen-mix", default="8,32,128",
                    help="continuous mode: generation budgets, cycled "
                         "through a seeded shuffle")
    ap.add_argument("--slots", default="0",
                    help="continuous mode: slot-arena size (0 = --batch; "
                         "'auto' runs the serving planner — needs "
                         "--arrival-hz)")
    ap.add_argument("--arrival-hz", type=float, default=0.0,
                    help="offered request rate for --slots auto")
    ap.add_argument("--policy", default="fifo", choices=list(POLICIES),
                    help="continuous mode: admission order")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=256,
                    help="continuous mode: prefill-chunk token budget")
    ap.add_argument("--split-cut", type=int, default=0,
                    help="L>0: split inference — UE runs blocks[:L], "
                         "ships coded INFER frames over loopback")
    add_plan_args(ap, flavor="serve")
    return ap.parse_args(argv)


def main(argv=None):
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    args = parse_args(argv)
    try:
        cfg = get_arch(args.arch).config(args.size)
    except ValueError as e:
        raise SystemExit(f"--size {args.size}: {e}")
    model = LM(cfg)
    params = model.init(jax.random.key(args.seed))
    cache_len = args.cache_len or (args.prompt_len + args.gen)
    if args.continuous:
        cache_len = args.cache_len or (
            args.prompt_len + max(int(g) for g in
                                  str(args.gen_mix).split(",") if g))
        return _continuous_serve(model, params, args, cache_len)

    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab, (args.batch, args.prompt_len))
    prompts = jnp.asarray(prompts, jnp.int32)
    if args.split_cut:
        return _split_serve(model, params, prompts, args, cache_len)
    frames = None
    if cfg.enc_layers:        # enc-dec: stub frames -> encoder memory
        frames = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.enc_seq, cfg.d_model)), jnp.float32)
    return _static_serve(model, params, prompts, frames, args, cache_len)


if __name__ == "__main__":
    main()
