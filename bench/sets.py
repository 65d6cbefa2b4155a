#!/usr/bin/env python3
"""Run a cell in sets of runs, one process a run, and report spreads.

    python bench/sets.py --workload <name> --seconds 30 --runs 6 --sets 2 \
        [--traced 3] [--first-seed N] [--out runs.jsonl]

Each set runs ``--runs`` seeds (the same seeds in every set) with
``--trace 0``; then ``--traced`` more seeds run with ``--trace 1``.  It
never imports JAX itself, so each child has the chips to itself.  For
every end-to-end metric it prints each set's median and spread (the
distance between the first and third quartiles of
``statistics.quantiles(values, n=4)``, as a share of the median).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def one(workload: str, seed: int, seconds: float, trace: int) -> dict:
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, capture_output=True, text=True)
    lines = proc.stdout.strip().splitlines()
    try:
        line = json.loads(lines[-1])
    except (IndexError, ValueError):
        line = None
    return {"seed": seed, "trace": trace, "rc": proc.returncode,
            "wall_s": time.perf_counter() - t0, "line": line,
            "stdout_tail": lines[-2:-1], "stderr_tail":
            proc.stderr.strip().splitlines()[-8:]}


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", type=int, default=0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [args.first_seed + 104_729 * i for i in range(args.runs)]
    records = []
    out = open(args.out, "a") if args.out else None
    for s in range(args.sets):
        for seed in seeds:
            rec = dict(one(args.workload, seed, args.seconds, 0), set=s)
            records.append(rec)
            print(json.dumps(rec), flush=True)
            if out:
                out.write(json.dumps(rec) + "\n")
                out.flush()
    for i in range(args.traced):
        seed = args.first_seed + 7 + 104_729 * (args.runs + i)
        rec = dict(one(args.workload, seed, args.seconds, 1), set="traced")
        records.append(rec)
        print(json.dumps(rec), flush=True)
        if out:
            out.write(json.dumps(rec) + "\n")
            out.flush()
    for s in range(args.sets):
        lines = [r["line"] for r in records if r["set"] == s and r["line"]]
        if len(lines) < 3:
            continue
        for name in lines[0]["metrics"]:
            vals = [ln["metrics"][name]["value"] for ln in lines]
            print(f"set {s} {name}: median {statistics.median(vals)!r} "
                  f"spread {spread(vals)!r} over {len(vals)} runs; "
                  f"correct {sum(ln['correct'] for ln in lines)}/"
                  f"{len(lines)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
