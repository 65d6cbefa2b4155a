"""Per-layer metrics: one reader per metric, ``bench/metrics/<name>.py``,
found by the metric's name in ``BENCHMARK.json``.

A reader is ``read(obs) -> float | None``.  ``obs`` is the run's
``Observation``; a reader that finds nothing to read returns ``None`` and
the metric is left out of the line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os

from harness.spec import BENCH_DIR


@dataclasses.dataclass
class Observation:
    cell: object           # spec.Cell
    peaks: dict            # peaks.PEAKS entry of the device
    chips: int
    run: dict              # what the run measured and counted
    trace: object | None   # trace.Summary of the window, or None


def read(name: str, obs: Observation):
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(obs)
