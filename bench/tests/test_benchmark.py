"""``BENCHMARK.json`` finds every file it names, and every per-layer
metric is reported beside the end-to-end metric it moves."""
import json
import os
import re

import pytest

from harness import metrics, spec

BENCH = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_files_exist(workload):
    cell = spec.load_cell(workload)
    assert cell.traffic["kind"] in ("train", "serve")
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "metrics",
                                           m["name"] + ".py"))


def test_names_and_layers():
    every = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
             + BENCH["per_layer"])
    assert all(NAME.match(e["name"]) for e in every)
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"], []).append(m["name"])
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])


def _obs(workload, run, trace=None):
    cell = spec.load_cell(workload)
    from harness.peaks import PEAKS
    return metrics.Observation(cell=cell, peaks=PEAKS["TPU v5 lite"],
                               chips=cell.chips, run=run, trace=trace)


def test_reader_with_nothing_to_read_returns_none():
    obs = _obs("qwen4b-train-1chip", {"steps": 3})
    for name in ("hop_exposed_ms", "wire_codec_roofline", "idle_share.train",
                 "decode_step_roofline"):
        assert metrics.read(name, obs) is None


def test_train_mfu_by_hand():
    obs = _obs("qwen4b-train-1chip", {"train_tokens_per_s": 20_000.0})
    want = 100 * 2.4465408e9 * 20_000 / 197e12
    assert metrics.read("train_mfu", obs) == pytest.approx(want, rel=1e-6)


def test_codec_roofline_by_hand():
    from harness import trace
    enc = ("a custom-call:tpu_custom_call (s8[4096,10,256], f32[4096,10,1])"
           " <- (bf16[4096,2560])")
    dec = ("b custom-call:tpu_custom_call bf16[4096,2560] <- "
           "(s8[4096,10,256], f32[4096,10,1])")
    s = trace.Summary(devices=[{"id": 0, "ops": [[enc, 0, 100_000],
                                                 [dec, 200_000, 100_000]],
                                "loops": [], "modules": []}],
                      host=[["bench.window", 0, 1_000_000]])
    obs = _obs("qwen4b-pipe4-int8", {"steps": 1}, trace=s)
    per_call = 4096 * 2560 * 3 + 4096 * 10 * 4
    want = 100 * 2 * per_call / 819e9 / 200e-6
    assert metrics.read("wire_codec_roofline", obs) == pytest.approx(want)
