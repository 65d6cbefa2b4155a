#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration, a traffic mix and
its limits, each a file under ``bench/``.  The run makes weights and
inputs from ``--seed``, warms up every shape the traffic uses (set-up),
measures for ``--seconds``, checks what the timed path produced against
the plain reference, and prints one JSON line last.  With ``--trace 1``
the window is traced and the line carries the per-layer metrics.

It refuses to run on anything but the TPUs the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

#: JAX's persistent compilation cache, inside the checkout at a fixed
#: path (the path is part of the cache's key), whatever the environment
#: names: the run writes nothing outside its checkout.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_cache():
    """Keep every compiled program in ``CACHE_DIR``, however quick."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def devices_or_exit(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def main(argv=None) -> int:
    args = _args(argv)
    from harness import spec
    cell = spec.load_cell(args.workload)
    use_cache()
    devices = devices_or_exit(cell.chips)
    from harness.peaks import peaks_for
    peaks = peaks_for(devices[0].device_kind)
    import repro  # noqa: F401  (the program under test must be there)
    from harness.clock import CompileClock
    clock = CompileClock()
    module = importlib.import_module("harness." + cell.traffic["kind"])
    run = module.run(cell, devices, args.seed, args.seconds,
                     t_start=T_START, clock=clock,
                     trace_dir=TRACE_DIR if args.trace else None)
    line = result_line(cell, run, devices, peaks, args.trace)
    print(f"generator lag p95: {run.get('generator_lag_p95_ms', 0.0)} ms; "
          f"compiles inside the window: {run['compiles_in_window']}; "
          f"setup_s {run['setup_s']}, window "
          f"{run.get('window_s', args.seconds)} s", flush=True)
    for name, value, limit in run["checks"]:
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def result_line(cell, run: dict, devices, peaks: dict, trace: int) -> dict:
    from harness import metrics as M
    from harness import trace as T
    summary = run.get("trace")
    if trace:
        obs = M.Observation(cell=cell, peaks=peaks, chips=len(devices),
                            run=run, trace=summary)
        values = {m["name"]: (M.read(m["name"], obs), m["unit"])
                  for m in cell.per_layer}
    else:
        values = {m["name"]: (run[m["name"]], m["unit"])
                  for m in cell.end_to_end}
    checks = run["checks"]
    correct = all(math.isfinite(v) and v <= lim for _, v, lim in checks)
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": run["attempted"],
            "failed": run["failed"],
            "metrics": {k: {"value": v, "unit": u}
                        for k, (v, u) in values.items() if v is not None},
            "device": device}
    if summary is not None:
        device.update(busy_s=T.busy_s(summary), window_s=T.window_s(summary))
        line["breakdown"] = T.breakdown(summary)
    line["checks"] = {name: {"value": v if math.isfinite(v) else str(v),
                             "limit": lim} for name, v, lim in checks}
    return line


if __name__ == "__main__":
    sys.exit(main())
