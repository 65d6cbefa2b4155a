"""Device time by the program's own names: its scopes and its spans.

The program marks its layers with ``jax.named_scope`` (``SCOPES``).  The
names reach each HLO instruction's ``metadata={op_name=...}``, in the
forward pass and in the backward (``jvp(attention)``,
``transpose(jvp(attention))``).  A trace's device operations carry the
instruction's name and not its metadata, so the map from an operation
to its scope comes from the compiled program's HLO text, which
``train_step_hlo`` and ``decode_step_hlo`` rebuild from the cell: the
same function at the same shapes and shardings, so a compile that the
run left in the persistent cache is loaded, not redone.

The serving engine marks its host work with ``TraceAnnotation`` spans
(``ENGINE_SPANS``), on the profiler's clock; ``idle_within`` puts the
device's idle time down to them in a summary that kept them
(``trace.reduce_xplane(path, spans=trace.SPANS + ENGINE_SPANS)``).
"""
from __future__ import annotations

import bisect
import re

import jax
import numpy as np

from harness import trace

#: the program's named scopes, by layer; a reader may ask for any other
#: name the program gives a scope (``with_scope``)
SCOPES = ("attention", "mlp", "head_loss", "pipeline.hop", "arena",
          "embed", "grad_accum", "optimizer")
#: ``ServingEngine``'s host spans; ``engine.step`` holds the others
ENGINE_SPANS = ("engine.step", "engine.schedule", "engine.admit",
                "engine.decode", "engine.sync", "engine.emit")
#: the programs the scopes are read in (``XLA Modules`` names)
TRAIN_STEP = re.compile(r"^jit_train_step\b")
DECODE_STEP = re.compile(r"^jit_step\b")

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = ")
_COMP = re.compile(r"^\s*(?:ENTRY )?%?([^\s(]+) .*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_ARGS = re.compile(r"^(?:\([^()]*\)|\S+) [\w\-]+\([^%)]*%([\w.\-]+)")
_WRAPPED = re.compile(r"^[\w.\-]*\((.*)\)$")


def op_names(hlo_text: str) -> dict:
    """``{instruction: op_name}`` of every instruction in a compiled
    module's HLO text.  A fusion without metadata takes that of its fused
    computation's root (else of its first instruction that has one); any
    other instruction without metadata, such as a copy the compiler put
    in, takes that of its first operand ("" where none has one)."""
    names, calls, operand, roots, firsts = {}, {}, {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            c = _COMP.match(line)
            if c and "=" not in line.split("{")[0]:
                comp = c.group(1)
            continue
        instr = m.group(1)
        op = _OP_NAME.search(line)
        if op:
            names[instr] = op.group(1)
            firsts.setdefault(comp, op.group(1))
            if line.lstrip().startswith("ROOT "):
                roots[comp] = op.group(1)
            continue
        names[instr] = ""
        called = _CALLS.search(line) if " fusion(" in line else None
        if called:
            calls[instr] = called.group(1)
        else:
            args = _ARGS.match(_LAYOUT.sub("", line[m.end():]))
            if args:
                operand[instr] = args.group(1)
    for instr, comp in calls.items():
        names[instr] = roots.get(comp) or firsts.get(comp) or ""
    for instr, src in operand.items():
        for _ in range(16):
            if names.get(src) or src not in operand:
                break
            src = operand[src]
        names[instr] = names.get(src, "")
    return names


def scope_of(op_name: str, names=SCOPES):
    """The innermost of ``names`` on the ``op_name`` path, else None.  A
    path component may be wrapped by a transformation:
    ``transpose(jvp(attention))`` is ``attention``."""
    found = None
    for comp in op_name.split("/"):
        while True:
            m = _WRAPPED.match(comp)
            if not m:
                break
            comp = m.group(1)
        if comp in names:
            found = comp
    return found


def scope_table(hlo_text: str, names=SCOPES) -> dict:
    """``{instruction: scope or None}`` of a compiled module, over the
    scopes ``names``."""
    return {i: scope_of(n, names) for i, n in op_names(hlo_text).items()}


def with_scope(scope: str) -> tuple:
    """``SCOPES``, and ``scope`` if it is not among them: the names a
    table is built over to read ``scope``.  Innermost wins, so a scope
    nested in one of ``SCOPES`` (``mlp/moe.experts``) takes its own
    operations from the outer one only in a table built to read it."""
    return SCOPES if scope in SCOPES else SCOPES + (scope,)


def instruction(table: dict, op: str):
    """The instruction of ``table`` a trace operation (its ``short_name``)
    ran, else None.  An operation the compiler derived from an
    instruction after the HLO was printed (``fusion.4.remat_uncompressed``)
    counts as that instruction."""
    name = op.split(" ", 1)[0]
    while name not in table and "." in name:
        head, tail = name.rsplit(".", 1)
        if tail.isdigit():
            return None
        name = head
    return name if name in table else None


def lookup(table: dict, op: str):
    """The scope of a trace operation, else None."""
    return table.get(instruction(table, op))


def module_ops(summary, module):
    """Per chip, the device operations of the window that ran inside a
    program whose name ``module`` (a compiled regex) matches."""
    lo, hi = summary.window()
    out = []
    for dev in summary.devices:
        spans = trace.union((a, b) for n, a, b in
                            trace._clip(dev["modules"], lo, hi)
                            if module.match(n))
        starts = [a for a, _ in spans]
        mine = []
        for name, a, b in trace._clip(dev["ops"], lo, hi):
            j = bisect.bisect_right(starts, a) - 1
            if j >= 0 and a < spans[j][1]:
                mine.append((name, a, min(b, spans[j][1])))
        out.append(mine)
    return out


def by_scope(summary, hlo_text: str, module, top: int = 10,
             names=SCOPES) -> dict:
    """Device seconds of the programs ``module`` matches, by scope of
    ``names`` (``None``: outside every scope), the operations outside
    every scope that took most time, and the share of operations whose
    instruction was not found in the HLO; each averaged over the chips."""
    table = scope_table(hlo_text, names)
    per_chip = module_ops(summary, module)
    n = len(per_chip)
    seconds, outside = {}, {}
    unknown = total = 0.0
    for ops in per_chip:
        for name, a, b in ops:
            s = lookup(table, name)
            d = (b - a) / n / 1e9
            seconds[s] = seconds.get(s, 0.0) + d
            total += d
            if s is None:
                outside[name] = outside.get(name, 0.0) + d
                if instruction(table, name) is None:
                    unknown += d
    return {"seconds": seconds, "total": total,
            "unknown_share": unknown / total if total else 0.0,
            "outside": sorted(outside.items(), key=lambda kv: -kv[1])[:top]}


def scope_seconds(summary, hlo_text: str, scope: str, module) -> float:
    """Device seconds of the window's operations under ``scope`` (any
    name the program gives a scope: ``with_scope``) in the programs
    ``module`` matches, averaged over the chips."""
    return by_scope(summary, hlo_text, module,
                    names=with_scope(scope))["seconds"].get(scope, 0.0)


def module_runs(summary, module) -> float:
    """Runs of the programs ``module`` matches that started in the
    window, averaged over the chips."""
    lo, hi = summary.window()
    return sum(sum(1 for n, t, _ in d["modules"]
                   if module.match(n) and lo <= t < hi)
               for d in summary.devices) / len(summary.devices)


def idle_within(summary, span_name: str) -> float:
    """Seconds inside the host spans named ``span_name`` (within the
    window) in which no operation ran on a chip, averaged over the
    chips."""
    lo, hi = summary.window()
    spans = trace.union((a, b) for _, a, b in trace._clip(
        [h for h in summary.host if h[0] == span_name], lo, hi))
    total = 0.0
    for dev in summary.devices:
        busy = trace.union((a, b) for _, a, b in trace._clip(
            dev["ops"] + dev.get("loops", []), lo, hi))
        starts = [a for a, _ in busy]
        for x, y in spans:
            j = max(0, bisect.bisect_right(starts, x) - 1)
            covered = 0
            while j < len(busy) and busy[j][0] < y:
                a, b = busy[j]
                covered += max(0, min(b, y) - max(a, x))
                j += 1
            total += (y - x) - covered
    return total / len(summary.devices) / 1e9


# ---------------------------------------------------------------------------
# the compiled programs of a cell, rebuilt after its run
# ---------------------------------------------------------------------------


def _shaped(shapes, shardings):
    return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), shapes, shardings)


def train_step_hlo(cell, devices) -> str:
    """HLO text of the train step as the window runs it: compiled for the
    state's placement, then again for the placement its own outputs
    give the state (the same program where the two agree)."""
    from harness import train
    prog = train.program(cell, devices)
    tr = cell.traffic
    tok = jax.ShapeDtypeStruct((tr["batch"], tr["seq"]), np.int32,
                               sharding=prog["batch_sharding"])
    batch = {"tokens": tok, "labels": tok}
    state = _shaped(prog["state_shapes"], prog["shardings"])
    compiled = prog["step"].lower(state, batch).compile()
    state = _shaped(prog["state_shapes"], compiled.output_shardings[0])
    return prog["step"].lower(state, batch).compile().as_text()


def decode_step_hlo(cell, devices) -> str:
    """HLO text of the serving engine's decode step (``jit_step``) at the
    cell's slots and cache length, with the engine's float32 arena."""
    from harness import spec
    from repro.models.lm import LM
    from repro.serving import kv
    from repro.serving.engine import ServingEngine
    tr = cell.traffic
    one = jax.sharding.SingleDeviceSharding(devices[0])
    model = LM(spec.family(cell.config).lm_config(cell.config,
                                                  name=cell.config_name))
    params = jax.eval_shape(model.init, jax.random.key(0))
    params = _shaped(params, jax.tree.map(lambda _: one, params))
    cache = jax.eval_shape(lambda: model.init_cache(
        tr["slots"], tr["cache_len"], np.float32))
    cache = _shaped(cache, jax.tree.map(lambda _: one, cache))
    lane = jax.ShapeDtypeStruct((tr["slots"],), np.int32, sharding=one)
    active = jax.ShapeDtypeStruct((tr["slots"],), bool, sharding=one)
    step = ServingEngine._build_step(type("Engine", (), {
        "model": model, "temperature": 0.0, "seed": 0,
        "axes": kv.slot_axes(model, tr["cache_len"], np.float32)})())
    return jax.jit(step).lower(params, cache, lane, active, lane, lane,
                               lane).compile().as_text()


def program_hlo(obs, build) -> str:
    """``build(cell, devices)``'s HLO text, made once per run: the
    readers of one run share it."""
    key = "hlo:" + build.__name__
    if key not in obs.run:
        obs.run[key] = build(obs.cell, jax.devices()[:obs.chips])
    return obs.run[key]


# ---------------------------------------------------------------------------
# what the per-layer readers share
# ---------------------------------------------------------------------------


def train_scope_ms(obs, scope: str):
    """Device ms per train step of the operations under ``scope`` (in
    ``SCOPES`` or not), averaged over the chips; None where the program
    has no such scope."""
    if obs.trace is None or obs.cell.traffic["kind"] != "train" \
            or not obs.run.get("steps"):
        return None
    seconds = scope_seconds(obs.trace, program_hlo(obs, train_step_hlo),
                            scope, TRAIN_STEP)
    return 1e3 * seconds / obs.run["steps"] if seconds > 0 else None


def decode_scope_ms(obs, scope: str):
    """Device ms per decode step of the operations under ``scope`` (in
    ``SCOPES`` or not) in the engine's ``jit_step``; None where the
    program has no such scope."""
    if obs.trace is None or obs.cell.traffic["kind"] != "serve":
        return None
    runs = module_runs(obs.trace, DECODE_STEP)
    if not runs:
        return None
    seconds = scope_seconds(obs.trace, program_hlo(obs, decode_step_hlo),
                            scope, DECODE_STEP)
    return 1e3 * seconds / runs if seconds > 0 else None
