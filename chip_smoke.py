#!/usr/bin/env python3
"""Smoke run of the main path on a TPU chip.

    python chip_smoke.py             # one chip: device, train, codec, serve
    python chip_smoke.py --chips 4   # four chips: device, 4-stage pipeline

Each phase drives the entry points a user calls (``repro.launch.train``,
``repro.launch.serve``, the wire codec) at qwen1.5-4b's published widths,
cut to one chip's share (``configs/qwen15_4b.py``: 4 of 40 layers, an
eighth of the vocabulary).  Weights and data come from ``--seed``.  A
failed check raises, so the script exits non-zero and never prints its
last line, which on success is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

It refuses to run on anything but a TPU.  Times it prints are smoke
timings of single runs, not benchmark results.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.models.common import AttentionPaths  # noqa: E402

ARCH = "qwen1.5-4b"

#: ``LM.init`` draws the head from N(0, 0.02^2) and the final RMSNorm
#: leaves each hidden row at unit RMS, so at init every logit is
#: N(0, sigma^2) with sigma^2 = 0.02^2 * d_model, and the expected loss
#: is E[logsumexp] - E[label logit] = ln(V) + sigma^2 / 2 (sigma^2 = 1.02
#: at d_model 2560, so 10.36 against ln(18992) = 9.85).
INIT_STD = 0.02
#: The first loss averages batch x seq token losses under one random head:
#: its spread around the expectation is a few hundredths; bf16 logits add
#: less.  0.15 also fails a head or vocabulary slice of the wrong size
#: (a factor 2 in V moves ln V by 0.69).
LOSS0_TOL = 0.15
#: Greedy tokens whose logits tie to within this gap may differ between a
#: batched and a solo decode on the chip (bf16 rounding); a wrong cache
#: row, position or weight moves logits by O(sigma) = O(1).
LOGIT_GAP_TOL = 0.1
#: bf16 keeps 8 significant bits (2^-8 relative).  The pipeline and the
#: gradient-accumulation reference round partial sums at different
#: points, which moves gradients by a few bf16 ulps; a relative L2 error
#: of 2^-6 is four of them, while a dropped micro-batch (1/8 of the
#: gradient), a wrong stage or a wrong hop moves gradients by far more.
#: Each leaf is measured against the larger of its own norm and its
#: share of the whole gradient's norm: a leaf whose gradient is mostly
#: cancellation (the key bias: softmax cancels a shift shared by every
#: key, up to RoPE's rotation of it) keeps only rounding noise in its
#: own norm.
GRAD_RTOL = 2.0 ** -6
#: Both losses average batch x seq token losses in float32 from bf16
#: logits; 1e-2 is a sixth of one bf16 ulp at 10.
LOSS_ATOL = 1e-2
#: int8 codes carry 1/254 of each 256-wide block's max; three coded hops
#: move the loss by far less than 0.05 (half a percent of ~10).
INT8_LOSS_ATOL = 0.05


def _fail(msg: str):
    raise RuntimeError(msg)


def run_phase(clock, name: str, fn, **kwargs):
    """Run one phase; print its wall seconds, its compile seconds, which
    programs it compiled or loaded from the persistent cache, and the
    path each attention call it traced took (``AttentionPaths``)."""
    before = clock.snapshot()
    t0 = time.perf_counter()
    with AttentionPaths() as paths:
        out = fn(**kwargs)
    wall = time.perf_counter() - t0
    seconds, compiled, loaded = clock.since(before)

    def names(counts):
        return ", ".join(f"{n} x{c}" if c > 1 else n
                         for n, c in sorted(counts.items())) or "none"
    print(f"{name}: passed in {wall:.2f} s wall; compile {seconds:.2f} s; "
          f"compiled: {names(compiled)}; from the persistent cache: "
          f"{names(loaded)}; attention paths traced: {names(paths)}",
          flush=True)
    return out


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def phase_device(want: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"device: JAX found no TPU (platform "
                         f"{d.platform!r}); this smoke run needs the chip")
    if len(devs) < want:
        raise SystemExit(f"device: {want} chips asked for, {len(devs)} "
                         "found")
    return info


def _peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# train: the unpipelined step with gradient accumulation, through train.main
# ---------------------------------------------------------------------------


def expected_first_loss(cfg) -> float:
    return math.log(cfg.vocab) + 0.5 * INIT_STD ** 2 * cfg.d_model


def phase_train(*, size: str = "chip", batch: int = 8, seq: int = 4096,
                microbatches: int = 8, steps: int = 4, seed: int = 0):
    from repro.configs import get_arch
    from repro.launch import train

    cfg = get_arch(ARCH).config(size)
    history = train.main([
        "--arch", ARCH, "--size", size, "--steps", str(steps),
        "--batch", str(batch), "--seq", str(seq),
        "--microbatches", str(microbatches), "--log-every", "1",
        "--seed", str(seed)])
    losses = [row["loss"] for row in history]
    if len(losses) != steps or not np.all(np.isfinite(losses)):
        _fail(f"train: losses {losses}")
    want = expected_first_loss(cfg)
    if abs(losses[0] - want) > LOSS0_TOL:
        _fail(f"train: first loss {losses[0]:.4f}, expected {want:.4f} "
              f"+- {LOSS0_TOL}")
    step_s = [row["step_s"] for row in history]
    steady = float(np.median(step_s[1:])) if steps > 1 else float("nan")
    out = {"losses": losses, "first_step_s": step_s[0],
           "steady_step_s": steady, "peak_bytes": _peak_bytes()}
    print(f"train: {ARCH} --size {size} batch {batch} x seq {seq}, "
          f"k={microbatches}: losses {[round(x, 4) for x in losses]} "
          f"(first expected {want:.4f} +- {LOSS0_TOL})", flush=True)
    print(f"train: smoke timing, not a benchmark: first step "
          f"{step_s[0]:.2f} s (compile included), steady step "
          f"{steady:.3f} s; peak_bytes_in_use {out['peak_bytes']}",
          flush=True)
    return out


# ---------------------------------------------------------------------------
# codec: the fused Pallas wire codec against the jnp path, at the hop shapes
# ---------------------------------------------------------------------------

#: pipeline hop [mb, seq, d] at mb 1 x seq 4096; a ragged 4094-row hop;
#: an odd INFER prompt chunk.
CODEC_SHAPES = ((1, 4096, 2560), (2, 2047, 2560), (1, 250, 2560))


def _e4m3_ulp(v):
    """Spacing of float8_e4m3fn values at |v| (3 mantissa bits; below the
    smallest normal 2^-6 the spacing is the subnormal step 2^-9)."""
    a = np.maximum(np.abs(v), 2.0 ** -6)
    return 2.0 ** (np.floor(np.log2(a)) - 3)


def phase_codec(*, shapes=CODEC_SHAPES, on_chip: bool = True, seed=0):
    from repro.kernels import ops
    from repro.parallel import wire

    dt = jnp.bfloat16
    report = []
    for i, shape in enumerate(shapes):
        x = (jax.random.normal(jax.random.key(seed + i), shape) * 2.0
             ).astype(dt)
        for wdt in ("int8", "fp8"):
            if on_chip:
                for name, fn, args in (
                        ("encode", ops.wire_encode, (x,)),
                        ("decode", ops.wire_decode, None)):
                    if args is None:
                        args = jax.eval_shape(
                            lambda x: ops.wire_encode(x, wire_dtype=wdt), x)
                        hlo = fn.lower(*args, out_dtype=dt).compile()
                    else:
                        hlo = fn.lower(*args, wire_dtype=wdt).compile()
                    if "tpu_custom_call" not in hlo.as_text():
                        _fail(f"codec: {name} {wdt} {shape} compiled "
                              "without the Pallas kernel")
            qf, sf = ops.wire_encode(x, wire_dtype=wdt)
            qj, sj = jax.jit(
                lambda x, w=wdt: wire.encode(x, w, impl="jnp"))(x)
            qf32 = np.asarray(qf.astype(jnp.float32))
            qj32 = np.asarray(qj.astype(jnp.float32))
            quantum = 1.0 if wdt == "int8" else _e4m3_ulp(
                np.maximum(np.abs(qf32), np.abs(qj32)))
            code_gap = float(np.max(np.abs(qf32 - qj32) / quantum))
            codes_differ = float(np.mean(qf32 != qj32))
            sf, sj = np.asarray(sf), np.asarray(sj)
            scale_rel = float(np.max(np.abs(sf - sj) / sj))
            yf = np.asarray(ops.wire_decode(qj, jnp.asarray(sj),
                                            out_dtype=dt), np.float32)
            yj = np.asarray(jax.jit(lambda q, s: wire.decode(
                q, s, dt, impl="jnp"))(qj, jnp.asarray(sj)), np.float32)
            dec_rel = float(np.max(np.abs(yf - yj)
                                   / np.maximum(np.abs(yj), 1e-30)))
            row = {"shape": list(shape), "wire": wdt,
                   "codes_differ": codes_differ, "max_code_gap": code_gap,
                   "max_scale_rel": scale_rel, "max_decode_rel": dec_rel}
            report.append(row)
            print(f"codec: {wdt} {shape}: codes differ {codes_differ:.3g} "
                  f"(max gap {code_gap:g} quantum), scale rel "
                  f"{scale_rel:.3g}, decode rel {dec_rel:.3g}", flush=True)
            # one quantum of code; float32 rounding of the scale (its
            # /qmax may run as a reciprocal multiply, 2 ulps); the decode
            # is q * s rounded once to bf16 (one bf16 ulp, 2^-7 relative)
            if code_gap > 1.0 or scale_rel > 2.0 ** -22 \
                    or dec_rel > 2.0 ** -7:
                _fail(f"codec: fused and jnp paths disagree: {row}")
    return report


# ---------------------------------------------------------------------------
# serve: the continuous-batching engine through serve.main vs solo decodes
# ---------------------------------------------------------------------------


def _teacher_forced_gap(model, params, prompt, tokens, cache_len) -> float:
    """Feed ``tokens`` after ``prompt`` through the batch-1 reference
    decode; return the largest gap between the best logit and the logit
    of the token that was emitted, over every step."""
    from repro.parallel.steps import make_decode_step
    decode = jax.jit(make_decode_step(model))
    logits, state = jax.jit(
        model.prefill_with_cache,
        static_argnames=("cache_len", "cache_dtype"))(
            params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]},
            cache_len=cache_len, cache_dtype=jnp.float32)
    tok = jnp.argmax(logits, axis=-1, keepdims=True).astype(jnp.int32)
    gap = 0.0
    for t in tokens:
        logits, state = decode(params, state, tok)
        row = np.asarray(logits[0], np.float32)
        gap = max(gap, float(row.max() - row[int(t)]))
        tok = jnp.full((1, 1), int(t), jnp.int32)
    return gap


def phase_serve(*, size: str = "chip", requests: int = 8,
                prompt_len: int = 256, gen_mix: str = "8,32,128",
                slots: int = 8, seed: int = 0):
    from repro.configs import get_arch
    from repro.launch import serve
    from repro.models.lm import LM
    from repro.serving.engine import solo_decode

    cache_len = prompt_len + max(int(g) for g in gen_mix.split(","))
    argv = ["--arch", ARCH, "--size", size, "--continuous",
            "--requests", str(requests), "--prompt-len", str(prompt_len),
            "--gen-mix", gen_mix, "--slots", str(slots),
            "--cache-len", str(cache_len), "--seed", str(seed),
            "--temperature", "0"]
    outputs = serve.main(argv)
    cfg = get_arch(ARCH).config(size)
    model = LM(cfg)
    params = model.init(jax.random.key(seed))    # the weights serve.main made
    reqs = serve.request_mix(cfg, serve.parse_args(argv))
    identical, gaps = 0, []
    for r in reqs:
        out = np.asarray(outputs[r.rid])
        if out.shape != (r.max_new_tokens,) or out.min() < 0 \
                or out.max() >= cfg.vocab:
            _fail(f"serve: request {r.rid} returned {out.shape} tokens "
                  f"in [{out.min()}, {out.max()}], asked for "
                  f"{r.max_new_tokens} in [0, {cfg.vocab})")
        ref = solo_decode(model, params, r.prompt, r.max_new_tokens,
                          cache_len=cache_len)
        if np.array_equal(out, ref):
            identical += 1
            continue
        # not bit-identical: every emitted token must still be the
        # reference's argmax up to a bf16-rounding tie
        gap = _teacher_forced_gap(model, params, r.prompt, out, cache_len)
        gaps.append(gap)
        if gap > LOGIT_GAP_TOL:
            _fail(f"serve: request {r.rid} emitted a token {gap:.4f} below "
                  f"the reference's best logit (tolerance {LOGIT_GAP_TOL})")
    print(f"serve: {len(reqs)} requests, prompts {prompt_len}, gen mix "
          f"{gen_mix}, {slots} slots: {identical}/{len(reqs)} bit-identical "
          f"to solo_decode"
          + (f"; others within logit gap {max(gaps):.4g} <= "
             f"{LOGIT_GAP_TOL}" if gaps else ""), flush=True)
    return {"identical": identical, "requests": len(reqs),
            "max_logit_gap": max(gaps) if gaps else 0.0}


# ---------------------------------------------------------------------------
# pipeline: train.py's 4-stage pipeline against the unpipelined gradients
# ---------------------------------------------------------------------------


def grad_errors(got, want) -> dict:
    """Per-leaf L2 error of ``got`` against ``want``, relative to the
    larger of the leaf's norm and its size's share of the whole tree's
    norm (see ``GRAD_RTOL``); keys are the leaves' paths."""
    want_leaves = [np.asarray(b, np.float32) for b in jax.tree.leaves(want)]
    total = math.sqrt(sum(float(np.sum(b * b)) for b in want_leaves))
    size = sum(b.size for b in want_leaves)
    out = {}
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            want_leaves):
        scale = max(float(np.linalg.norm(b)),
                    total * math.sqrt(b.size / size), 1e-30)
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        out[name] = float(np.linalg.norm(np.asarray(a, np.float32) - b)
                          / scale)
    return out


def _misplaced(blocks, mesh, stages: int) -> list:
    """Every block leaf [L, ...] is split over the pod axis: the device at
    pod index i holds layers [i*L/S, (i+1)*L/S) and nothing else."""
    pod = mesh.axis_names.index("pod")
    pod_of = {d: idx[pod] for idx, d in np.ndenumerate(mesh.devices)}
    out = []
    for leaf in jax.tree.leaves(blocks):
        per = leaf.shape[0] // stages
        got = [(pod_of[s.device], s.index[0].start or 0, s.data.shape[0])
               for s in leaf.addressable_shards]
        if any(start != i * per or n != per for i, start, n in got):
            out.append(f"pipeline: block leaf {leaf.shape} placed as "
                       f"(pod index, first layer, layers) {got}")
    return out


def phase_pipeline(*, size: str = "chip", batch: int = 8, seq: int = 4096,
                   k: int = 8, stages: int = 4, steps: int = 2,
                   seed: int = 0):
    from repro.analysis.staticcheck import audit_hlo_text
    from repro.configs import get_arch
    from repro.launch import train
    from repro.launch.mesh import make_host_mesh
    from repro.models.lm import LM
    from repro.parallel.compat import mesh_context
    from repro.parallel.pipeline import PipelineSpec, make_pipelined_loss
    from repro.parallel.steps import make_lm_loss
    from repro.training.microbatch import microbatched_value_and_grad

    cfg = get_arch(ARCH).config(size)
    model = LM(cfg)
    params = model.init(jax.random.key(seed))         # train.run's weights
    batch0 = next(train.build_batch_iter(cfg, batch, seq, seed))
    # reference: the unpipelined step's gradient accumulation over k
    (loss_ref, _), g_ref = jax.jit(microbatched_value_and_grad(
        make_lm_loss(model), k))(params, batch0)
    loss_ref = float(loss_ref)
    g_ref = jax.tree.map(np.asarray, g_ref)
    mesh = make_host_mesh(pod=stages)
    result = {"loss_ref": loss_ref}
    # both codecs run before any check fails the phase, so one (costly)
    # four-chip run reports every disagreement
    problems = []
    for wire in ("none", "int8"):
        spec = PipelineSpec(num_stages=stages, microbatches=k,
                            virtual_stages=1, wire_dtype=wire)
        loss_fn = make_pipelined_loss(model, spec, mesh=mesh)
        placed = train.place_pipeline_state({"params": params},
                                            mesh)["params"]
        with mesh_context(mesh):
            compiled = jax.jit(jax.value_and_grad(
                lambda p, b: loss_fn(p, b)[0])).lower(placed,
                                                      batch0).compile()
            vio, stats = audit_hlo_text(
                compiled.as_text(), pod_size=1, num_stages=stages,
                virtual_stages=1, wire_dtype=wire, d_model=cfg.d_model,
                act_dtype=cfg.dtype, checks=("perm", "leak"))
            if vio or not stats["n_hop_cp"]:
                problems.append(
                    f"pipeline[{wire}]: tick-loop collectives {stats}: "
                    f"{[(v.cls, v.detail) for v in vio]}")
            loss_p, g_p = compiled(placed, batch0)
        loss_p = float(loss_p)
        rel = grad_errors(g_p, g_ref)
        worst = max(rel, key=rel.get)
        print(f"pipeline[{wire}]: S={stages} k={k} loss {loss_p:.5f} vs "
              f"unpipelined {loss_ref:.5f}; worst grad error "
              f"{rel[worst]:.3g} ({worst}); {stats['n_hop_cp']} hop "
              f"collective-permutes, {len(vio)} other cross-stage "
              "collective(s) in the tick loop", flush=True)
        if not math.isfinite(loss_p):
            problems.append(f"pipeline[{wire}]: loss {loss_p}")
        if wire == "none" and (abs(loss_p - loss_ref) > LOSS_ATOL
                               or rel[worst] > GRAD_RTOL):
            problems.append(
                f"pipeline[none]: loss {loss_p} vs {loss_ref} (atol "
                f"{LOSS_ATOL}), grad {worst} rel {rel[worst]} (rtol "
                f"{GRAD_RTOL})")
        if wire == "int8" and abs(loss_p - result["none"]["loss"]) \
                > INT8_LOSS_ATOL:
            problems.append(
                f"pipeline[int8]: loss {loss_p} vs none "
                f"{result['none']['loss']} (atol {INT8_LOSS_ATOL})")
        history, state = train.run([
            "--arch", ARCH, "--size", size, "--steps", str(steps),
            "--batch", str(batch), "--seq", str(seq), "--log-every", "1",
            "--seed", str(seed), "--pipeline-stages", str(stages),
            "--pipeline-k", str(k), "--virtual-stages", "1",
            "--wire-dtype", wire])
        losses = [row["loss"] for row in history]
        if not np.all(np.isfinite(losses)) \
                or abs(losses[0] - loss_p) > LOSS_ATOL:
            problems.append(f"pipeline[{wire}]: train.py losses {losses}, "
                            f"first step should be {loss_p}")
        misplaced = _misplaced(state["params"]["blocks"], mesh, stages)
        problems += misplaced
        print(f"pipeline[{wire}]: train.py losses "
              f"{[round(x, 4) for x in losses]}; "
              + (f"{len(misplaced)} block leaves misplaced" if misplaced
                 else f"each chip holds its own stage's "
                      f"{cfg.num_layers // stages} layer(s)")
              + f"; smoke timing: steady step "
              f"{history[-1]['step_s']:.3f} s", flush=True)
        result[wire] = {"loss": loss_p, "worst_grad_error": rel[worst],
                        "train_losses": losses}
    if problems:
        _fail("\n".join(problems))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-stage pipeline phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.cache import CompileClock, use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    device = phase_device(args.chips)
    clock = CompileClock()
    if args.chips == 4:
        run_phase(clock, "pipeline", phase_pipeline, seed=args.seed)
    else:
        run_phase(clock, "train", phase_train, seed=args.seed)
        run_phase(clock, "codec", phase_codec, seed=args.seed)
        run_phase(clock, "serve", phase_serve, seed=args.seed)
    print(f"compile: {clock.seconds:.2f} s over {clock.programs} programs, "
          f"{clock.cache_hits} from the persistent cache", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
