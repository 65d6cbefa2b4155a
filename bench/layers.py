#!/usr/bin/env python3
"""One traced run of a cell, with the device time put down to the
program's own scopes and spans.

    python3 bench/layers.py --workload <name> --seed <n> --seconds <s>

It is ``bench/run.py --trace 1`` (the same set-up, window, checks and
result line), and besides it prints one more JSON line, ``{"layers":
...}``:

- each program the per-layer readers read (the train step, or the
  engine's ``jit_step``): device ms per run by scope (``harness/scopes``),
  the time outside every scope and its longest operations;
- training: the share of the step's device time under ``attention``,
  ``mlp``, ``head_loss`` and ``pipeline.hop``;
- serving: the device's idle seconds inside each of the engine's spans
  (``engine.step`` and its children, each child's own and the parent's
  self time), against the idle inside the benchmark's ``step_once``;
  ``engine.step`` idle per step; and the lanes' occupancy over the window
  from the engine's ``lane_steps`` and ``decode_steps``;
- the traced window's tokens per second, to set against an untraced run
  of the same seed.
"""
from __future__ import annotations

import contextlib
import glob
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402  (puts src/ on the path; its clock starts here)


def main(argv=None) -> int:
    import jax
    from harness import scopes, serve, train, trace

    args = run._args(argv)
    kept: dict = {}
    engines: list = []

    @contextlib.contextmanager
    def capture(out_dir: str, result: dict):
        """``trace.capture``, keeping the engine's spans beside the
        benchmark's and the engine's lane counters at both ends."""
        shutil.rmtree(out_dir, ignore_errors=True)
        jax.profiler.start_trace(out_dir)
        before = [_lanes(e) for e in engines]
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()
        kept["lanes"] = [(_lanes(e), b) for e, b in zip(engines, before)]
        files = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        full = trace.reduce_xplane(files[0],
                                   spans=trace.SPANS + scopes.ENGINE_SPANS)
        kept["summary"] = full
        result["trace"] = trace.Summary(
            devices=full.devices,
            host=[h for h in full.host if h[0] in trace.SPANS])
        shutil.rmtree(out_dir, ignore_errors=True)

    build, train_run, serve_run = serve.build, train.run, serve.run

    def keep_engine(*a, **kw):
        engine, params = build(*a, **kw)
        engines.append(engine)
        return engine, params

    def keep_run(fn):
        def wrapped(*a, **kw):
            kept["run"] = fn(*a, **kw)
            return kept["run"]
        return wrapped

    trace.capture = capture
    serve.build = keep_engine
    train.run, serve.run = keep_run(train_run), keep_run(serve_run)
    run.main(list(argv if argv is not None else sys.argv[1:])
             + ["--trace", "1"])
    from harness import spec
    cell = spec.load_cell(args.workload)
    line = {"layers": layers(cell, kept, jax.devices()[:cell.chips])}
    print(json.dumps(line), flush=True)
    return 0


def _lanes(engine):
    return engine.decode_steps, getattr(engine, "lane_steps", None)


def _ms(seconds: float, runs: float) -> float:
    return 1e3 * seconds / runs if runs else 0.0


def layers(cell, kept: dict, devices) -> dict:
    from harness import scopes, trace
    summary, result = kept["summary"], kept["run"]
    serving = cell.traffic["kind"] == "serve"
    module = scopes.DECODE_STEP if serving else scopes.TRAIN_STEP
    build = scopes.decode_step_hlo if serving else scopes.train_step_hlo
    # the per-layer readers of the result line left it in the run's record
    hlo = result.get("hlo:" + build.__name__) or build(cell, devices)
    runs = scopes.module_runs(summary, module)
    table = scopes.by_scope(summary, hlo, module)
    total = table["total"]
    out = {"program": module.pattern, "runs": runs,
           "ms_per_run": _ms(total, runs),
           "by_scope_ms": {str(k): _ms(v, runs)
                           for k, v in table["seconds"].items()},
           "outside_share": table["seconds"].get(None, 0.0) / total
           if total else None,
           "unknown_share": table["unknown_share"],
           "outside_top_ms": [[n, _ms(v, runs), v / total]
                              for n, v in table["outside"]],
           "busy_s": trace.busy_s(summary), "window_s": trace.window_s(summary)}
    if serving:
        idle = {name: scopes.idle_within(summary, name)
                for name in scopes.ENGINE_SPANS + ("step_once",)}
        children = ("engine.schedule", "engine.admit", "engine.decode")
        lo, hi = summary.window()
        steps = sum(1 for n, t, _ in summary.host
                    if n == "engine.step" and lo <= t < hi)
        out.update(
            idle_s=idle,
            idle_self_s={
                "engine.step": idle["engine.step"]
                - sum(idle[c] for c in children),
                "engine.decode": idle["engine.decode"]
                - idle["engine.sync"] - idle["engine.emit"]},
            engine_steps=steps,
            engine_gap_ms=_ms(idle["engine.step"], steps),
            serve_tokens_per_s=result["serve_tokens_per_s"])
        (d1, l1), (d0, l0) = kept["lanes"][0]
        if l1 is not None and d1 > d0:
            out["lane_occupancy"] = 100.0 * (l1 - l0) / (
                cell.traffic["slots"] * (d1 - d0))
    else:
        scoped = sum(v for k, v in table["seconds"].items()
                     if k in ("attention", "mlp", "head_loss",
                              "pipeline.hop"))
        out.update(scoped_share=scoped / total if total else None,
                   train_tokens_per_s=result["train_tokens_per_s"])
    return out


if __name__ == "__main__":
    sys.exit(main())
