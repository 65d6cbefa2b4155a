"""From a profiler trace to the numbers the per-layer metrics read.

``capture`` wraps the measured window in ``jax.profiler`` and reduces
the ``.xplane.pb`` it writes to a ``Summary``: for each chip the device
operations (the "XLA Ops" line) and programs ("XLA Modules"), and the
benchmark's own host spans, all on the profiler's one clock in
nanoseconds.  The functions below take a ``Summary`` and nothing else,
so ``bench/tests`` checks them on a recorded one.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import shutil

WINDOW = "bench.window"
#: the benchmark's host spans (jax.profiler.TraceAnnotation names)
SPANS = (WINDOW, "step_fn", "step_once", "submit")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Summary:
    """``devices``: per chip ``{"id", "ops", "loops", "modules"}``; ``ops``
    are the operations that contain no other (``[name, t0, dur]``, name
    as ``short_name`` gives it), ``loops`` the control flow that holds
    them (while loops, conditionals, calls), ``modules`` the programs.
    ``host``: the benchmark's spans."""
    devices: list
    host: list

    def window(self) -> tuple:
        spans = [(t, t + d) for n, t, d in self.host if n == WINDOW]
        if not spans:
            raise ValueError("the trace holds no bench.window span")
        return spans[0]


_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTR = re.compile(r"^%?(\S+) = (\([^()]*\)|\S+) ([\w\-]+)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_OPERANDS = re.compile(r"operand_layout_constraints=\{([^{}]*)\}")


def short_name(text: str) -> str:
    """``name kind`` of an HLO instruction as the trace prints it (the
    whole instruction), e.g. ``fusion.588 fusion``.  A custom call adds
    its target and its result and operand types, which tell kernels
    apart: ``_lambda_.1 custom-call:tpu_custom_call (s8[4096,10,256],
    f32[4096,10,1]) <- (bf16[4096,2560])``."""
    flat = _LAYOUT.sub("", text)
    m = _INSTR.match(flat)
    if not m:
        return text[:120]
    name, result, kind = m.group(1), m.group(2), m.group(3)
    target = _TARGET.search(text)
    if not target:
        return f"{name} {kind}"
    ops = _OPERANDS.search(flat)
    return (f"{name} {kind}:{target.group(1)} {result}"
            + (f" <- ({ops.group(1)})" if ops else ""))


def _split_nested(events):
    """(leaf operations, operations that contain another)."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    leaves, outer = [], []
    for i, (name, t, d) in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        holds = nxt is not None and nxt[1] < t + d and \
            nxt[1] + nxt[2] <= t + d
        (outer if holds else leaves).append([name, t, d])
    return leaves, outer


def reduce_xplane(path: str, spans=SPANS) -> Summary:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = {"id": int(m.group(1)), "ops": [], "loops": [],
                   "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"], dev["loops"] = _split_nested(
                        [short_name(e.name), e.start_ns, e.duration_ns]
                        for e in line.events)
                elif line.name == "XLA Modules":
                    dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events if e.name in spans]
    devices.sort(key=lambda d: d["id"])
    return Summary(devices=devices, host=host)


@contextlib.contextmanager
def capture(out_dir: str, result: dict):
    """Trace the block; on exit put its ``Summary`` in ``result["trace"]``.
    The raw trace is deleted once reduced."""
    import jax
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    result["trace"] = reduce_xplane(files[0])
    shutil.rmtree(out_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def _clip(events, lo, hi):
    out = []
    for name, t, d in events:
        a, b = max(t, lo), min(t + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def union(intervals) -> list:
    """Merged [a, b) intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(dev: dict, lo, hi) -> float:
    return float(sum(b - a for a, b in union(
        (a, b) for _, a, b in _clip(dev["ops"] + dev.get("loops", []),
                                    lo, hi))))


def busy_s(summary: Summary) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    lo, hi = summary.window()
    return sum(busy_ns(d, lo, hi) for d in summary.devices) \
        / len(summary.devices) / 1e9


def window_s(summary: Summary) -> float:
    lo, hi = summary.window()
    return (hi - lo) / 1e9


def idle_share(summary: Summary) -> float:
    return 1.0 - busy_s(summary) / window_s(summary)


def op_seconds(summary: Summary, match) -> float:
    """Device seconds of the operations whose name ``match`` accepts,
    summed over the window and averaged over the chips."""
    lo, hi = summary.window()
    total = 0.0
    for dev in summary.devices:
        total += sum(b - a for n, a, b in _clip(dev["ops"], lo, hi)
                     if match(n))
    return total / len(summary.devices) / 1e9


def op_count(summary: Summary, match) -> float:
    """Operations whose name ``match`` accepts, averaged over the chips."""
    lo, hi = summary.window()
    return sum(sum(1 for n, _, _ in _clip(d["ops"], lo, hi) if match(n))
               for d in summary.devices) / len(summary.devices)


def exposed_seconds(summary: Summary, match) -> float:
    """Seconds of the operations ``match`` accepts during which no other
    operation runs on that chip, averaged over the chips."""
    lo, hi = summary.window()
    total = 0.0
    for dev in summary.devices:
        ops = _clip(dev["ops"], lo, hi)
        mine = union((a, b) for n, a, b in ops if match(n))
        others = union((a, b) for n, a, b in ops if not match(n))
        starts = [x for x, _ in others]
        for a, b in mine:
            covered = 0
            j = max(0, bisect.bisect_right(starts, a) - 1)
            while j < len(others) and others[j][0] < b:
                x, y = others[j]
                covered += max(0, min(b, y) - max(a, x))
                j += 1
            total += (b - a) - covered
    return total / len(summary.devices) / 1e9


def module_seconds(summary: Summary, match) -> float:
    """Device seconds of the programs whose name ``match`` accepts,
    averaged over the chips."""
    lo, hi = summary.window()
    return sum(sum(b - a for n, a, b in _clip(d["modules"], lo, hi)
                   if match(n)) for d in summary.devices) \
        / len(summary.devices) / 1e9


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The device operations that took most time, and the device's idle
    time by the host span it fell in (``host`` where the benchmark had
    no span open), each averaged over the chips."""
    lo, hi = summary.window()
    n = len(summary.devices)
    per_op: dict = {}
    idle: dict = {}
    spans = sorted(((t, t + d, name) for name, t, d in summary.host
                    if name != WINDOW), key=lambda s: s[0])
    for dev in summary.devices:
        for name, a, b in _clip(dev["ops"], lo, hi):
            per_op[name] = per_op.get(name, 0.0) + (b - a) / 1e9 / n
        edge = lo
        busy = _clip(dev["ops"] + dev.get("loops", []), lo, hi)
        for a, b in union((a, b) for _, a, b in busy) + [[hi, hi]]:
            if a > edge:
                for label, sec in _label_gap(edge, a, spans):
                    idle[label] = idle.get(label, 0.0) + sec / n
            edge = max(edge, b)

    def top_of(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:top]
    return {"device_ops": top_of(per_op), "idle_gaps": top_of(idle)}


def _label_gap(a, b, spans):
    """Split the idle interval [a, b) by the host spans over it."""
    out, edge = [], a
    for x, y, name in spans:
        if y <= edge or x >= b:
            continue
        if x > edge:
            out.append(("host", (x - edge) / 1e9))
        hi = min(y, b)
        out.append((name, (hi - max(x, edge)) / 1e9))
        edge = hi
        if edge >= b:
            break
    if edge < b:
        out.append(("host", (b - edge) / 1e9))
    return out
