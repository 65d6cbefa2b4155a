"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration, a traffic mix and (through the per-layer
metrics) readers; each is a file of its own, and so is the model family
that the configuration's ``model_type`` names:

    bench/configs/<config>.json     sizes, as run, with the source's keys
    bench/families/<model_type>.py  the family: the program's LMConfig,
                                    the leaves, the plain reference and
                                    the FLOP counts (``family``)
    bench/traffic/<traffic>.json    parameters for the module its "kind"
                                    names (harness/train.py, serve.py)
    bench/limits/<workload>.json    the limits that decide ``correct``
    bench/metrics/<metric>.py       one reader per per-layer metric

Adding a cell is adding files and entries; no file here needs an edit.
A configuration of a family the benchmark has not run costs a family
file, a configuration file, its traffic, its limits, readers for what
it adds, and the entries in ``BENCHMARK.json``; no file that is there
changes.  A family module gives:

    lm_config(config, name)            the program's ``LMConfig``
    param_shapes(config)               leaf name -> shape; the program's
                                       tree has to be this one
    Reference(config, seed, devices, *, adam=None, precision="f32",
              microbatches=1, rows=None, drop_exchange=False)
                                       the plain reference: ``train_step``,
                                       ``leaf_norms``, ``change_norms``
                                       and, for serving cells, ``logits``
                                       (``harness/reference.py`` has the
                                       options' meaning)
    train_flops_per_token(config, seq)
    decode_step_least(config, positions, weight_itemsize, kv_itemsize)
    prefill_flops(config, prompt_len)  the work, as ``harness/flops.py``
                                       counts a dense decoder's
    published(config)                  the published widths of the model
                                       that the file's ``source`` names
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
#: where ``family`` finds ``<model_type>.py``
FAMILIES_DIR = os.path.join(BENCH_DIR, "families")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list      # the BENCHMARK.json entries this cell reports
    per_layer: list


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"--workload {workload!r} is not in BENCHMARK.json "
                         f"(cells: {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    here = os.path.join(root, "bench")

    def reports(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return Cell(
        name=workload, chips=int(w["chips"]),
        config_name=w["config"],
        config=_load(os.path.join(root, cfg_entry["file"])),
        traffic=_load(os.path.join(here, "traffic", w["traffic"] + ".json")),
        limits=_load(os.path.join(here, "limits", workload + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if reports(m)],
        per_layer=[m for m in bench["per_layer"] if reports(m)])


_families: dict = {}


def family(config: dict):
    """The family module that the configuration's ``model_type`` names,
    ``FAMILIES_DIR/<model_type>.py``, loaded once per file."""
    model_type = config.get("model_type")
    path = os.path.join(FAMILIES_DIR, f"{model_type}.py")
    if path not in _families:
        if not os.path.exists(path):
            raise ValueError(f"model_type {model_type!r}: no family file "
                             f"{os.path.relpath(path, ROOT)}")
        mod_spec = importlib.util.spec_from_file_location(
            "bench_family_" + str(model_type).replace(".", "_"), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _families[path] = mod
    return _families[path]
