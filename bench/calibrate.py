#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip, in one process.

    python bench/calibrate.py --workload <name> --seeds 12 --faults 3 \
        [--seconds 30] [--out readings.json]

For each of ``--seeds`` seeds it reads the numbers ``correct`` compares
for the program itself (the lower readings), and for the first
``--faults`` seeds the same numbers for the control and the planted
faults, each put in the program's place against the float32 reference
(the upper readings):

* training: the reference in float8 (``control``), the reference on half
  of the batch's rows with the mean over the rest (``half_batch``) and,
  across chips, the reference with the exchange between chips left out
  (``no_exchange``).  A step that returns its state unchanged reads 1 on
  ``change_norm_gap`` by construction and needs no run.
* serving: a short window of the cell's own traffic per seed (long
  enough to finish its longest requests), then the gap of the token that
  the float8 reference puts first at each served position.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]


def _seed(i: int) -> int:
    return 2_000_000_000 + 7919 * i


def calibrate_train(cell, devices, seeds, faults) -> list:
    from harness import train
    tr = cell.traffic
    n = tr["ref_steps"]
    prog = train.program(cell, devices)
    out = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        feed, rows = train.materialize(prog, cell, seed)
        got = train.first_steps(prog["step"], prog, cell, feed, seed)[0]
        feed = None
        want = train.reference_readings(
            train.reference(cell, seed, devices), rows, n)
        rec = {"seed": seed, "program": _vals(train.compare(
            got, want, cell.limits)), "ref_losses": want["losses"],
            "program_losses": got["losses"],
            "left_out": train.left_out(want),
            "ref_grad_norms": want["grad"]}
        if i < faults:
            variants = {"control": {"precision": "fp8"},
                        "half_batch": {"rows": tr["batch"] // 2}}
            if len(devices) > 1:
                variants["no_exchange"] = {"drop_exchange": True}
            for name, kw in variants.items():
                alt = train.reference_readings(
                    train.reference(cell, seed, devices, **kw), rows, n)
                rec[name] = _vals(train.compare(alt, want, cell.limits))
        rec["seconds"] = time.perf_counter() - t0
        print(json.dumps(rec), flush=True)
        out.append(rec)
    return out


def calibrate_serve(cell, devices, seeds, faults, seconds) -> list:
    import numpy as np
    from harness import serve, spec
    from harness.clock import CompileClock
    clock = CompileClock()
    out = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        run = serve.run(cell, devices, seed, seconds, t_start=t0,
                        clock=clock)
        rec = {"seed": seed, "program": {k: v for k, v, _ in run["checks"]},
               "checked_tokens": run["checked_tokens"],
               "ttft_p95_ms": run["ttft_p95_ms"],
               "itl_p95_ms": run["itl_p95_ms"]}
        if i < faults:
            fam = spec.family(cell.config)
            ref = fam.Reference(cell.config, seed, devices[:1])
            ctl = fam.Reference(cell.config, seed, devices[:1],
                                precision="fp8")
            rec["control"] = {"served_logit_gap": serve.served_gap(
                ref, run["checked"], cell.traffic["cache_len"],
                control=ctl)[0]}
            # a token altered where it is produced: the next id
            alt = [(p, t0_, (np.asarray(o) + 1) % cell.config["vocab_size"])
                   for p, t0_, o in run["checked"]]
            rec["altered_token"] = {"served_logit_gap": serve.served_gap(
                ref, alt, cell.traffic["cache_len"])[0]}
            ref = ctl = None      # the next seed's engine needs the memory
        rec["seconds"] = time.perf_counter() - t0
        print(json.dumps(rec), flush=True)
        out.append(rec)
    return out


def _vals(checks) -> dict:
    return {k: v for k, v, _ in checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=0,
                    help="index of the first seed (seeds differ by index)")
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from run import devices_or_exit, use_cache
    from harness import spec
    use_cache()
    cell = spec.load_cell(args.workload)
    devices = devices_or_exit(cell.chips)
    seeds = [_seed(args.first + i) for i in range(args.seeds)]
    if cell.traffic["kind"] == "serve":
        out = calibrate_serve(cell, devices, seeds, args.faults,
                              args.seconds)
    else:
        out = calibrate_train(cell, devices, seeds, args.faults)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
