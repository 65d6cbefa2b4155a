"""End-to-end training driver.

Examples
--------
# laptop-scale smoke training (CPU, reduced config):
PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b --size smoke \
    --steps 200 --batch 16 --seq 64

# one TPU chip's share of the published model (configs/qwen15_4b.py):
... --size chip --batch 8 --seq 4096 --microbatches 8

# the paper's C2P2SL k-microbatch gradient accumulation:
... --microbatches 8

# production mesh shapes are exercised by dryrun.py; this driver trains
# for real on whatever devices exist.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SIZES, get_arch
from repro.data import TokenTaskConfig, token_batches
from repro.models.lm import LM
from repro.parallel.steps import make_lm_train_step
from repro.training import checkpoint as ckpt_lib
from repro.training.optim import adamw, cosine_schedule


def build_batch_iter(cfg, batch: int, seq: int, seed: int = 0):
    task = TokenTaskConfig(vocab=cfg.vocab)
    gen = token_batches(task, batch, seq, seed=seed)
    if cfg.family == "vlm":
        rng = np.random.default_rng(seed + 1)
        def it():
            for b in gen:
                b["patch_embeds"] = rng.standard_normal(
                    (batch, cfg.num_patches, cfg.d_model)).astype(np.float32)
                yield b
        return it()
    if cfg.family == "audio":
        rng = np.random.default_rng(seed + 2)
        def it():
            for b in gen:
                b["frames"] = rng.standard_normal(
                    (batch, cfg.enc_seq, cfg.d_model)).astype(np.float32)
                yield b
        return it()
    return gen


def _parse_auto_int(value, flag: str):
    """'auto' | int-string | int | None -> 'auto' | int | None."""
    if value is None or isinstance(value, int):
        return value
    s = str(value).strip().lower()
    if s == "auto":
        return "auto"
    try:
        return int(s)
    except ValueError:
        raise SystemExit(
            f"{flag} must be an integer or 'auto', got {value!r}")


def _load_plan_hints(plan_hints):
    """Measured planner hints (benchmarks/ppermute_probe.py JSON) -> dict."""
    if not plan_hints:
        return None
    try:
        with open(plan_hints) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise SystemExit(f"--plan-hints {plan_hints}: {e}")
    hints = doc.get("planner_hints", doc)
    if not isinstance(hints, dict):
        raise SystemExit(
            f"--plan-hints {plan_hints}: expected a JSON object with a "
            "planner_hints dict (see benchmarks/ppermute_probe.py)")
    return hints


def plan_inputs_for(*, cfg, batch: int, seq: int, pipeline_stages: int,
                    plan_roofline: str | None = None,
                    plan_hints: str | None = None):
    """Base ``PlanInputs`` for this run: the dry-run record's measured
    costs when ``--plan-roofline`` names one, else the compile-free
    config estimate; ``--plan-hints`` overlays either.  Returns
    ``(inputs, source_label)`` — also the calibration anchor the online
    re-planner (``--replan``) drifts from."""
    import dataclasses as _dc

    from repro.analysis import autotune
    extra_hints = _load_plan_hints(plan_hints)
    if plan_roofline:
        try:
            record = autotune.load_record(plan_roofline)
            inp = autotune.plan_inputs_from_record(
                record, num_stages=pipeline_stages,
                num_layers=cfg.num_layers, extra_hints=extra_hints)
        except (OSError, ValueError) as e:   # unreadable / unpipelined record
            raise SystemExit(f"--plan-roofline {plan_roofline}: {e}")
        inp_src = plan_roofline
    else:
        hints = extra_hints or {}
        inp = autotune.plan_inputs_from_cfg(
            cfg, batch=batch, seq=seq, num_stages=pipeline_stages,
            hop_overhead_s=hints.get("hop_overhead_s"),
            link_bw_Bps=hints.get("link_bw_Bps"))
        inp_src = "config estimate (no --plan-roofline)"
    # a micro-batch needs at least one sample row
    return _dc.replace(inp, k_cap=max(1, min(inp.k_cap, batch))), inp_src


def resolve_pipeline_plan(*, pipeline_stages: int, pipeline_k,
                          virtual_stages, cfg, batch: int, seq: int,
                          plan_roofline: str | None = None,
                          wire_dtype: str = "none",
                          plan_hints: str | None = None):
    """Resolve the (S, k, v, wire) pipeline decision from flags + planner.

    Returns ``(PipelineSpec | None, info)``.  ``info`` records where each
    value came from — ``flag`` (hand-supplied), ``auto`` (the roofline
    planner, asked for explicitly), ``auto:default`` (k was unset: the
    planner picks it, replacing the old silent k=4 default), or
    ``default`` (v unset stays 1; wire unset stays 'none').  The
    resolved cell rides ``info["plan_cell"]`` as the versioned
    ``autotune.Plan`` JSON (the single plan currency; ``spec.plan``
    round-trips it); when the planner runs, ``info`` additionally
    carries the full ``AutoPlan`` evidence under ``"plan"``.
    ``plan_hints`` overlays measured planner hints (the ppermute-probe
    calibration) on the record's own.
    """
    k_arg = _parse_auto_int(pipeline_k, "--pipeline-k")
    v_arg = _parse_auto_int(virtual_stages, "--virtual-stages")
    wire = "none" if wire_dtype is None else str(wire_dtype).strip().lower()
    if wire != "auto":
        from repro.parallel import wire as wire_mod
        try:
            wire = wire_mod.validate_wire_dtype(wire)
        except (ValueError, NotImplementedError) as e:
            raise SystemExit(f"--wire-dtype: {e}")
    if pipeline_stages <= 1:
        if v_arg not in (None, 1):
            raise SystemExit(
                "--virtual-stages requires --pipeline-stages > 1 "
                "(interleaving subdivides pipeline stages)")
        if k_arg is not None:
            raise SystemExit(
                "--pipeline-k requires --pipeline-stages > 1 "
                "(use --microbatches for plain gradient accumulation)")
        if wire != "none":
            raise SystemExit(
                "--wire-dtype requires --pipeline-stages > 1 (the codec "
                "compresses the inter-stage pipeline hop)")
        return None, {"enabled": False}
    if isinstance(k_arg, int) and k_arg < 1:
        raise SystemExit(f"--pipeline-k {k_arg} must be >= 1")
    if isinstance(v_arg, int) and v_arg < 1:
        raise SystemExit(f"--virtual-stages {v_arg} must be >= 1")
    k_src = "flag" if isinstance(k_arg, int) \
        else ("auto" if k_arg == "auto" else "auto:default")
    v_src = "flag" if isinstance(v_arg, int) \
        else ("auto" if v_arg == "auto" else "default")
    wire_src = "auto" if wire == "auto" \
        else ("flag" if wire != "none" else "default")

    from repro.analysis.autotune import Plan
    from repro.parallel.pipeline import PipelineSpec
    if isinstance(k_arg, int) and (isinstance(v_arg, int) or v_arg is None) \
            and wire != "auto":
        try:
            cell = Plan(stages=pipeline_stages, k=k_arg,
                        v=v_arg if v_arg else 1, wire_dtype=wire)
        except ValueError as e:
            raise SystemExit(str(e))
        spec = PipelineSpec.from_plan(cell)
        return spec, {"enabled": True, "k": spec.microbatches,
                      "v": spec.virtual_stages, "wire": spec.wire_dtype,
                      "k_source": k_src, "v_source": v_src,
                      "wire_source": wire_src,
                      "plan_cell": cell.to_json(), "plan": None}

    inp, inp_src = plan_inputs_for(
        cfg=cfg, batch=batch, seq=seq, pipeline_stages=pipeline_stages,
        plan_roofline=plan_roofline, plan_hints=plan_hints)
    try:
        spec, plan = PipelineSpec.auto_plan(
            inp,
            k_fixed=k_arg if isinstance(k_arg, int) else None,
            v_fixed=v_arg if isinstance(v_arg, int)
            else (1 if v_arg is None else None),
            wire_dtype=wire)
    except ValueError as e:               # e.g. S*v does not divide layers
        raise SystemExit(str(e))
    return spec, {"enabled": True, "k": spec.microbatches,
                  "v": spec.virtual_stages, "wire": spec.wire_dtype,
                  "k_source": k_src, "v_source": v_src,
                  "wire_source": wire_src, "roofline": inp_src,
                  "plan_cell": spec.plan.to_json(),
                  "plan": plan.to_dict()}


def place_pipeline_state(state, mesh):
    """Put each stage's blocks (and their optimizer and error-feedback
    state) on that stage's devices before the first step; everything
    else is replicated over the pod axis."""
    from repro.parallel.compat import NamedSharding, PartitionSpec as P
    from repro.parallel.sharding import ShardingPolicy
    shardings = ShardingPolicy(
        mesh, pod_is_pipeline=True).train_state_shardings(state)
    if "wire_ef" in state:     # [S, ticks, mb, seq, d]: one slot per stage
        shardings["wire_ef"] = NamedSharding(mesh, P("pod"))
    return jax.device_put(state, shardings)


def main(argv=None):
    """CLI entry point; returns the logged metric rows."""
    return run(argv)[0]


def run(argv=None):
    """Parse ``argv`` and train; returns ``(logged metric rows, final
    train state)``."""
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--size", default="smoke", choices=SIZES)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1,
                    help="the paper's k (gradient accumulation)")
    from repro.launch.plan_args import add_plan_args, replan_config
    add_plan_args(ap, flavor="train")
    ap.add_argument("--replan-trace", default=None,
                    help="scripted link drift for --replan: JSON with "
                         "{'steps': [...], 'bw_Bps': [...]} (a "
                         "wireless.channel.BandwidthTrace) fed to the "
                         "re-planner as per-step bandwidth observations "
                         "— the deterministic drift driver for tests "
                         "and the replan_drift benchmark")
    ap.add_argument("--compress-grads", action="store_true",
                    help="int8 block-quantized gradients with error "
                         "feedback before the optimizer update "
                         "(training/compress.py; EPSL's BP-payload "
                         "compression generalized to the DP axis)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args(argv)

    try:
        cfg = get_arch(args.arch).config(args.size)
    except ValueError as e:
        raise SystemExit(f"--size {args.size}: {e}")
    model = LM(cfg)
    params = model.init(jax.random.key(args.seed))
    opt = adamw(cosine_schedule(args.lr, warmup=20, total=args.steps),
                grad_clip=1.0)
    state = {"params": params, "opt_state": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    if args.compress_grads:
        from repro.training.compress import init_error_fb
        state["error_fb"] = init_error_fb(params)

    pipeline, plan_info = resolve_pipeline_plan(
        pipeline_stages=args.pipeline_stages,
        pipeline_k=args.pipeline_k,
        virtual_stages=args.virtual_stages,
        cfg=cfg, batch=args.batch, seq=args.seq,
        plan_roofline=args.plan_roofline,
        wire_dtype=args.wire_dtype,
        plan_hints=args.plan_hints)
    if pipeline is not None:
        from repro.parallel.pipeline import wire_ef_zeros
        ef = wire_ef_zeros(cfg, pipeline, args.batch, args.seq)
        if ef is not None:     # top-k wire codec: EF rides the train state
            state["wire_ef"] = ef

    # resume-from-checkpoint (fault-tolerance entry point)
    if args.ckpt_dir:
        last = ckpt_lib.latest_step(args.ckpt_dir)
        if last is not None:
            # checkpoints taken BEFORE --compress-grads / a top-k wire
            # codec carry no error-feedback entry; restore everything
            # else and let the residual restart from zero (its natural
            # initial state)
            fresh = {}
            while True:
                try:
                    state = ckpt_lib.restore(args.ckpt_dir, last, state)
                    break
                except KeyError as e:
                    missing = [key for key in ("error_fb", "wire_ef")
                               if key in state and key in str(e)]
                    if not missing:
                        raise
                    fresh[missing[0]] = state.pop(missing[0])
                    print(f"checkpoint predates {missing[0]} — "
                          "error feedback restarts at zero")
            state.update(fresh)
            print(f"resumed from step {last}")

    mesh = None
    if pipeline is not None:
        if args.microbatches != 1:
            raise SystemExit(
                "--microbatches (gradient accumulation) and "
                "--pipeline-stages are mutually exclusive: the pipeline "
                "micro-batches with --pipeline-k instead")
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(pod=args.pipeline_stages)
        state = place_pipeline_state(state, mesh)
        line = (f"pipeline: S={pipeline.num_stages} "
                f"k={pipeline.microbatches} [{plan_info['k_source']}] "
                f"v={pipeline.virtual_stages} [{plan_info['v_source']}] "
                f"wire={pipeline.wire_dtype} [{plan_info['wire_source']}]")
        if plan_info.get("plan"):
            p = plan_info["plan"]
            line += (f"  modeled {p['wall_s'] * 1e3:.1f} ms/batch, "
                     f"{p['speedup']:.2f}x vs unpipelined, "
                     f"bubble {p['bubble']:.3f}")
        print(line, flush=True)
    if args.plan_out:
        with open(args.plan_out, "w") as f:
            json.dump(plan_info, f, indent=1)

    replan_cfg = replan_config(args)
    replanner = cell_cache = trace = None
    if replan_cfg is not None:
        if pipeline is None:
            raise SystemExit("--replan requires --pipeline-stages > 1 "
                             "(the re-planner moves the pipeline plan "
                             "cell; there is no cell without a pipeline)")
        from repro.parallel.pipeline import PipelineSpec
        from repro.training.replan import (PlanCellCache, Replanner,
                                           carry_state)
        inp, _ = plan_inputs_for(
            cfg=cfg, batch=args.batch, seq=args.seq,
            pipeline_stages=args.pipeline_stages,
            plan_roofline=args.plan_roofline, plan_hints=args.plan_hints)
        replanner = Replanner(inp, pipeline.plan, replan_cfg)
        if args.replan_trace:
            from repro.wireless.channel import BandwidthTrace
            try:
                with open(args.replan_trace) as f:
                    doc = json.load(f)
                trace = BandwidthTrace(steps=tuple(doc["steps"]),
                                       bw_Bps=tuple(doc["bw_Bps"]))
            except (OSError, KeyError, ValueError,
                    json.JSONDecodeError) as e:
                raise SystemExit(f"--replan-trace {args.replan_trace}: {e}")
        # jitted train step per plan cell: re-entering a cell is a cache
        # hit, so a switch costs one compile at most once per cell
        cell_cache = PlanCellCache(lambda p: jax.jit(make_lm_train_step(
            model, opt, microbatches=1,
            pipeline=PipelineSpec.from_plan(p), mesh=mesh,
            compress=args.compress_grads)))
        print(f"replan: {replan_cfg.describe()}"
              + (f" trace={args.replan_trace}" if trace else ""),
              flush=True)
        step_fn = cell_cache.get(pipeline.plan)
    else:
        step_fn = jax.jit(make_lm_train_step(model, opt,
                                             microbatches=args.microbatches,
                                             pipeline=pipeline, mesh=mesh,
                                             compress=args.compress_grads))
    it = build_batch_iter(cfg, args.batch, args.seq, args.seed)

    history = []
    t0 = time.perf_counter()
    start = int(state["step"])
    warm = False       # first step after a (re)compile is not a sample
    for i in range(start, args.steps):
        ts = time.perf_counter()
        state, mets = step_fn(state, next(it))
        if replanner is not None:
            jax.block_until_ready(mets["loss"])
            if warm:   # drop compile-tainted samples from the EWMA feed
                replanner.observe_step(0, time.perf_counter() - ts)
            warm = True
            if trace is not None:
                replanner.observe_bandwidth(trace.at(i + 1))
            switch = replanner.maybe_replan(i + 1)
            if switch is not None:
                print(f"replan @ step {switch.step}: {switch.old} -> "
                      f"{switch.new}  modeled "
                      f"{switch.old_wall_s * 1e3:.1f} -> "
                      f"{switch.new_wall_s * 1e3:.1f} ms/batch "
                      f"({switch.gain:.0%} gain)", flush=True)
                state = carry_state(state, switch.new, cfg=cfg,
                                    batch=args.batch, seq=args.seq)
                step_fn = cell_cache.get(switch.new)
                warm = False
        if args.log_every and (i + 1) % args.log_every == 0:
            row = {k: float(v) for k, v in mets.items()}  # waits for it
            # step_s: this step's dispatch to its metrics on the host
            # (compile included on the first step of a cell)
            row.update(step=i + 1, step_s=time.perf_counter() - ts,
                       wall_s=time.perf_counter() - t0)
            history.append(row)
            print(f"step {i+1:5d}  loss {row['loss']:.4f}  "
                  f"wall {row['wall_s']:.1f}s", flush=True)
        if args.ckpt_dir and args.ckpt_every \
                and (i + 1) % args.ckpt_every == 0:
            ckpt_lib.save(args.ckpt_dir, i + 1, state)
            ckpt_lib.prune(args.ckpt_dir)
    if replanner is not None:
        print(f"replan: {replanner.evals} evals, "
              f"{len(replanner.switches)} switch(es), "
              f"{cell_cache.misses} cell compile(s); "
              f"final {replanner.current}", flush=True)
        if args.plan_out:     # re-write with the switch log appended
            plan_info["replan"] = replanner.to_json()
            with open(args.plan_out, "w") as f:
                json.dump(plan_info, f, indent=1)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(history, f, indent=1)
    return history, state


if __name__ == "__main__":
    main()
