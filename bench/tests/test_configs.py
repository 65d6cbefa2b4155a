"""The configuration files against their families: the program's
registry of widths, and the published widths that each family gives."""
import dataclasses
import json
import os

import pytest

from harness import spec
from repro.configs import get_arch

BENCH = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))


def _file(name):
    entry = {c["name"]: c for c in BENCH["configs"]}[name]
    return json.load(open(os.path.join(spec.ROOT, entry["file"]))), entry


def test_one_chip_config_is_the_registrys_chip_share():
    cfg, _ = _file("qwen1.5-4b")
    want = get_arch("qwen1.5-4b").config("chip")
    got = spec.family(cfg).lm_config(cfg, name=want.name)
    assert got == want


def test_four_stage_config_is_four_chip_shares():
    cfg, _ = _file("qwen1.5-4b-4stage")
    chip = get_arch("qwen1.5-4b").config("chip")
    got = spec.family(cfg).lm_config(cfg, name=chip.name)
    assert got == dataclasses.replace(chip, num_layers=4 * chip.num_layers)


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_keeps_published_widths_and_lists_its_cuts(name):
    cfg, entry = _file(name)
    published = spec.family(cfg).published(cfg)
    changed = sorted(k for k, v in published.items() if cfg[k] != v)
    assert changed == sorted(entry["reduced"]) == sorted(cfg["reduced"])
    for key in cfg["reduced"]:
        assert cfg["reduced"][key][:2] == [published[key], cfg[key]]
