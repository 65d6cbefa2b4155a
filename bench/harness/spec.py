"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration, a traffic mix and (through the per-layer
metrics) readers; each is a file of its own:

    bench/configs/<config>.json     sizes, as run, with the source's keys
    bench/traffic/<traffic>.json    parameters for the module its "kind"
                                    names (harness/train.py, serve.py)
    bench/limits/<workload>.json    the limits that decide ``correct``
    bench/metrics/<metric>.py       one reader per per-layer metric

Adding a cell is adding files and entries; no file here needs an edit.
"""
from __future__ import annotations

import dataclasses
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


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


def lm_config(config: dict, name: str = "bench"):
    """The program's ``LMConfig`` for a configuration file written with
    the Hugging Face ``config.json`` keys of a dense decoder."""
    from repro.models.config import LMConfig
    if config.get("model_type") not in ("qwen2",):
        raise ValueError(f"model_type {config.get('model_type')!r}: this "
                         "harness maps only dense qwen2 decoders")
    return LMConfig(
        name=name, family="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab=config["vocab_size"],
        qkv_bias=bool(config["attention_bias"]),
        act=config["hidden_act"], norm="rmsnorm",
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=config["torch_dtype"])
