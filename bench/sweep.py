#!/usr/bin/env python3
"""Offer a serving cell's traffic at several fixed rates, to find the
highest rate the engine sustains (the knee), in one process.

    python bench/sweep.py --workload <name> --rates 4,6,8 --seconds 20

For each rate it runs one window of the cell's traffic at that rate and
prints what was served, the tails, and the backlog when arrivals
stopped: below the knee the backlog stays at about the slots in use;
above it the queue grows through the window.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2_100_000_000)
    args = ap.parse_args(argv)
    from run import devices_or_exit, use_cache
    from harness import serve, spec
    from harness.clock import CompileClock
    use_cache()
    serve.DRAIN_S = 0.0       # drain for one window at most
    base = spec.load_cell(args.workload)
    devices = devices_or_exit(base.chips)
    clock = CompileClock()
    for rate in (float(r) for r in args.rates.split(",")):
        cell = copy.deepcopy(base)
        cell.traffic.update(rate_per_s=rate, drain=True)
        cell.limits = dict(cell.limits, unfinished_requests=0)
        run = serve.run(cell, devices, args.seed, args.seconds,
                        t_start=time.perf_counter(), clock=clock)
        keys = ("requests", "failed", "backlog_at_end", "serve_tokens_per_s",
                "ttft_p95_ms", "itl_p95_ms", "decode_iter_ms",
                "admit_iter_ms", "generator_lag_p95_ms", "window_wall_s",
                "setup_s", "memory_peak_bytes")
        print(json.dumps({"rate_per_s": rate,
                          **{k: run[k] for k in keys},
                          "checks": run["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
