"""Serving cells: the program's ``ServingEngine`` under open-loop traffic.

Requests come from ``make_requests``: a fixed multiset of prompt and
output lengths and a fixed multiset of inter-arrival gaps (exponential
quantiles, so the arrivals are Poisson-like at the traffic file's rate),
each shuffled by the seed.  Every seed offers the same work in another
order.  Arrivals stop at the window's end; requests in flight are
drained for one more window (at least ``DRAIN_S``), unless the traffic
file says ``"drain": false``, as for load above the engine's knee, whose
queue only grows.  A request is timed from when it was due, so a late
generator or a stalled engine shows as latency.

After the drain, with the engine freed, the reference runs once over a
sample of finished requests (the longest among them) and reads, at each
position the engine served, how far the served token's logit lies below
the reference's best.  The engine's first generated token (the greedy
pick of the prefill, which it feeds to the first decode and does not
emit) is served work too, and is read the same way.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import spec, stats, weights
from harness.clock import CompileClock


def _counts(weights_, n: int) -> list:
    """Largest-remainder split of n by the weights."""
    raw = [w * n / sum(weights_) for w in weights_]
    out = [int(math.floor(r)) for r in raw]
    for i in sorted(range(len(raw)), key=lambda i: out[i] - raw[i])[
            :n - sum(out)]:
        out[i] += 1
    return out


def make_requests(tr: dict, seed: int, seconds: float, vocab: int) -> list:
    """[{rid, due, prompt, max_new}] for one window; ``due`` in seconds
    from the window's start."""
    n = max(1, round(tr["rate_per_s"] * seconds))
    rng = np.random.default_rng([int(seed) & (2 ** 64 - 1), 7])
    prompts = np.repeat(tr["prompt_lens"], _counts(tr["prompt_weights"], n))
    outs = np.repeat(tr["output_lens"], _counts(tr["output_weights"], n))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps)
    due = np.cumsum(gaps)
    due = due * (seconds * (n - 0.5) / n) / due[-1]
    prompts, outs = rng.permutation(prompts), rng.permutation(outs)
    return [{"rid": i, "due": float(due[i]), "max_new": int(outs[i]),
             "prompt": weights.token_rows(seed, 1, int(prompts[i]), vocab,
                                          stream=1000 + i)[0]}
            for i in range(n)]


def warm_shapes(tr: dict, slots: int) -> list:
    """(rows, prompt length) of every prefill chunk the scheduler can form
    from this traffic: it groups requests of one length while the chunk
    budget lasts, and always admits the head request."""
    budget = tr["prefill_chunk_tokens"]
    return [(r, p) for p in tr["prompt_lens"]
            for r in range(1, min(slots, max(1, budget // p)) + 1)]


def build(cell, devices, seed: int):
    from repro.models.lm import LM
    from repro.serving.engine import ServingEngine

    tr, cfg_json = cell.traffic, cell.config
    model = LM(spec.family(cfg_json).lm_config(cfg_json,
                                               name=cell.config_name))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    one = jax.sharding.SingleDeviceSharding(devices[0])
    params = jax.jit(lambda key: weights.make_params(key, shapes),
                     out_shardings=one)(weights.seed_key(seed))
    engine = ServingEngine(model, params, slots=tr["slots"],
                           cache_len=tr["cache_len"], temperature=0.0,
                           seed=seed,
                           prefill_chunk_tokens=tr["prefill_chunk_tokens"])
    return engine, params


def warm_up(engine, tr: dict, vocab: int):
    """Compile every program the window can call: each prefill chunk
    shape, the row copies, and the decode step."""
    from repro.serving.scheduler import Request
    rid = -1
    for rows, plen in warm_shapes(tr, engine.slots):
        reqs = []
        for _ in range(rows):
            reqs.append(Request(rid=rid, prompt=np.zeros(plen, np.int32),
                                max_new_tokens=1))
            rid -= 1
        engine.run(reqs)
    jax.block_until_ready(engine.cache)


def run(cell, devices, seed: int, seconds: float, *, t_start: float,
        clock: CompileClock, trace_dir: str | None = None,
        alter_token=None) -> dict:
    """One run of a serving cell.  ``alter_token`` (tests only) changes
    the tokens the engine emits, as a faulty engine would."""
    from repro.serving.scheduler import Request

    tr, cfg_json = cell.traffic, cell.config
    vocab = cfg_json["vocab_size"]
    engine, params = build(cell, devices, seed)
    warm_up(engine, tr, vocab)
    plan = make_requests(tr, seed, seconds, vocab)
    first_tok = {}
    admit = engine._admit_chunk

    def admit_and_note(chunk):
        admit(chunk)
        for slot in np.flatnonzero(engine.active):
            req = engine._tenant[int(slot)]
            first_tok.setdefault(req.rid, int(engine.tokens[slot]))
    engine._admit_chunk = admit_and_note
    if alter_token is not None:
        decode = engine._decode_once

        def decode_and_alter():
            decode()
            for req in engine._tenant.values():
                out = engine.outputs[req.rid]
                if out:
                    out[-1] = alter_token(out[-1])
        engine._decode_once = decode_and_alter

    recs = {p["rid"]: {"rid": p["rid"], "due": p["due"], "first": None,
                       "times": [],
                       "accepted": False, "lag": None} for p in plan}
    iters = []            # (host seconds, admitted a chunk)
    decode_positions = []  # per decode step: each served lane's position
    compiles0, compile_s = clock.programs, clock.seconds
    result: dict = {}
    clocks = {}

    def window():
        t0 = time.perf_counter()
        result["setup_s"] = t0 - t_start
        for r in recs.values():
            r["due"] += t0
        t_stop = t0 + seconds + (max(seconds, DRAIN_S)
                                 if tr.get("drain", True) else 0.0)
        clocks.update(t0=t0, t_end=t0 + seconds, t_stop=t_stop)
        i, inflight = 0, set()
        while True:
            now = time.perf_counter()
            if now >= t_stop:
                break
            while i < len(plan) and recs[i]["due"] <= now:
                p = plan[i]
                with jax.profiler.TraceAnnotation("submit"):
                    ok = engine.submit(Request(rid=p["rid"],
                                               prompt=p["prompt"],
                                               max_new_tokens=p["max_new"]))
                recs[i].update(accepted=ok, lag=now - recs[i]["due"])
                if ok:
                    inflight.add(i)
                i += 1
            if not (len(engine.scheduler) or engine.active.any()):
                if i >= len(plan):
                    break
                time.sleep(max(0.0, min(recs[i]["due"], t_stop)
                               - time.perf_counter()))
                continue
            chunks = engine.prefill_chunks
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("step_once"):
                engine.step_once()
            te = time.perf_counter()
            iters.append((te - ts, engine.prefill_chunks != chunks))
            served = []
            for rid in list(inflight):
                out = engine.outputs.get(rid)
                if out is None:
                    continue
                rec = recs[rid]
                for _ in range(len(out) - len(rec["times"])):
                    rec["times"].append(te)
                    served.append(len(plan[rid]["prompt"])
                                  + len(rec["times"]) - 1)
                if rec["times"] and rec["first"] is None:
                    rec["first"] = rec["times"][0]
                if rid in engine.done:
                    inflight.discard(rid)
            if served:
                decode_positions.append(served)

    if trace_dir:
        from harness.trace import capture
        with capture(trace_dir, result):
            window()
    else:
        window()
    wall = time.perf_counter()
    t0, t_end, t_stop = clocks["t0"], clocks["t_end"], clocks["t_stop"]

    allr = list(recs.values())
    ttft = stats.ttfts(allr, t_stop)
    itl = stats.gaps(allr)
    lags = [r["lag"] for r in allr if r["lag"] is not None]
    finished = [rid for rid in recs if rid in engine.done]
    tokens_in_window = sum(1 for r in allr for t in r["times"] if t <= t_end)
    p95 = stats.percentile(ttft, 95)
    decode_iters = [s for s, adm in iters if not adm]
    admit_iters = [s for s, adm in iters if adm]
    result.update(
        compile_s=compile_s, window_wall_s=wall - t0,
        compiles_in_window=clock.programs - compiles0,
        ttft_p95_ms=p95 * 1e3 if math.isfinite(p95) else MISSED_MS,
        itl_p95_ms=stats.percentile(itl, 95) * 1e3 if itl else MISSED_MS,
        serve_tokens_per_s=tokens_in_window / seconds,
        attempted=len(plan),
        failed=(len(plan) - len(finished) if tr.get("drain", True)
                else sum(1 for r in allr if not r["accepted"])),
        generator_lag_p95_ms=stats.percentile(lags, 95) * 1e3,
        requests=len(plan),
        backlog_at_end=sum(1 for r in allr if r["accepted"] and (
            r["rid"] not in engine.done or r["times"][-1] > t_end)),
        decode_iter_ms=(1e3 * float(np.mean(decode_iters))
                        if decode_iters else None),
        admit_iter_ms=(1e3 * float(np.mean(admit_iters))
                       if admit_iters else None),
        decode_positions=decode_positions,
        prefilled=[len(plan[r["rid"]]["prompt"]) for r in recs.values()
                   if r["times"]],
        weight_itemsize=jax.tree.leaves(params)[0].dtype.itemsize,
        kv_itemsize=jax.tree.leaves(engine.cache)[0].dtype.itemsize,
        memory_peak_bytes=max((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0) for d in devices))

    served = {rid: (plan[rid]["prompt"], first_tok[rid],
                    np.asarray(engine.done[rid])) for rid in finished}
    engine.cache = engine.params = params = None
    engine = None
    checked = [served[r] for r in sample_requests(served, seed,
                                                  tr["check_requests"])]
    ref = spec.family(cfg_json).Reference(cfg_json, seed, devices[:1])
    gap, tokens = served_gap(ref, checked, tr["cache_len"])
    result.update(checked=checked, checked_tokens=tokens)
    result["checks"] = [("served_logit_gap", gap,
                         cell.limits["served_logit_gap"])]
    if tr.get("drain", True):
        # every request was due in the window and waited for: one that
        # never finished is an answer that never came
        result["checks"].append(("unfinished_requests",
                                 float(result["failed"]),
                                 cell.limits["unfinished_requests"]))
    return result


#: Requests in flight when arrivals stop are drained for one more window,
#: and for at least this long.
DRAIN_S = 60.0
#: A p95 that falls on a request with no first token (or an empty
#: sample) has no finite value; it is reported as this many ms.
MISSED_MS = 1e9


def sample_requests(served: dict, seed: int, k: int) -> list:
    """The finished request with the most prompt and served tokens, and
    k - 1 more drawn from the seed."""
    rids = sorted(served)
    if not rids:
        return []
    longest = max(rids, key=lambda r: len(served[r][0]) + len(served[r][2]))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng([int(seed) & (2 ** 64 - 1), 11])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


@jax.jit
def _gaps(logits, targets, mask):
    """Per row: best logit minus the target token's logit (0 off mask)."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return jnp.where(mask, best - got, 0.0)


def sequences(prompt, tok0, out, length: int):
    """(inputs [length], targets [length], mask [length]) for one served
    request: row j of the logits predicts targets[j]; the mask marks the
    rows whose target the engine served (tok0, then every output
    token)."""
    full = np.concatenate([prompt, [tok0], out]).astype(np.int32)
    inputs = np.zeros(length, np.int32)
    targets = np.zeros(length, np.int32)
    mask = np.zeros(length, bool)
    n = len(full) - 1
    inputs[:n], targets[:n] = full[:-1], full[1:]
    mask[len(prompt) - 1:n] = True
    return inputs, targets, mask


def served_gap(ref, served: list, length: int, control=None):
    """Widest gap, over the served tokens of ``served``, between the
    reference's best logit and the served token's.  With ``control`` (a
    lower-precision reference) the served token is replaced by the one
    the control puts first at that position."""
    worst, count = 0.0, 0
    for prompt, tok0, out in served:
        inputs, targets, mask = sequences(prompt, tok0, out, length)
        logits = ref.logits(inputs)
        if control is not None:
            targets = np.asarray(jnp.argmax(control.logits(inputs), -1))
        gaps = np.asarray(_gaps(logits, jnp.asarray(targets),
                                jnp.asarray(mask)))
        worst = max(worst, float(gaps.max()))
        count += int(mask.sum())
    # nothing served is nothing shown correct
    return (worst if count else math.inf), count
