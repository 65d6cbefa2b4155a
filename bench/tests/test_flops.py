"""FLOP and byte counts against hand counts."""
import json
import os

import pytest

from harness import flops, spec


def _cfg(name):
    return json.load(open(os.path.join(spec.BENCH_DIR, "configs",
                                       name + ".json")))


def test_matmul_params_by_hand():
    # per layer: q, k, v, o 4 x 2560^2 = 26,214,400; MLP 3 x 2560 x 6912 =
    # 53,084,160; head 2560 x 18,992 = 48,619,520
    assert flops.matmul_params(_cfg("qwen1.5-4b")) == \
        4 * (26_214_400 + 53_084_160) + 48_619_520


@pytest.mark.parametrize("name,gflop", [("qwen1.5-4b", 2.4465),
                                        ("qwen1.5-4b-4stage", 8.9110)])
def test_train_flops_per_token(name, gflop):
    # 6 x 365.8 M + 6 x 4 x 4096 x 2560 = 2.4465 G (one chip);
    # 6 x 1,317.4 M + 6 x 16 x 4096 x 2560 = 8.9110 G (four stages)
    got = flops.train_flops_per_token(_cfg(name), 4096) / 1e9
    assert got == pytest.approx(gflop, abs=1e-4)


def test_decode_least_bytes_by_hand():
    cfg = _cfg("qwen1.5-4b")
    # two lanes at positions 9 and 99 read 10 + 100 positions of f32 KV:
    # 2 (k, v) x 4 layers x 20 heads x 128 x 4 B = 81,920 B a position
    f, b = flops.decode_step_least(cfg, [9, 99], 4, 4)
    assert b == 365_813_760 * 4 + 110 * 81_920
    assert f == 2 * 365_813_760 * 2 + 4 * 4 * 20 * 128 * 110


def test_codec_bytes_by_hand():
    # bf16 in, int8 out, one f32 scale per 256 values
    assert flops.codec_bytes(4096, 2560, 256, 2) == \
        4096 * 2560 * 3 + 4096 * 10 * 4


def test_prefill_counts_the_head_once():
    cfg = _cfg("qwen1.5-4b")
    head = 2560 * 18_992
    assert flops.prefill_flops(cfg, 1) == 2 * 365_813_760 + 2 * 4 * 2560
    assert flops.prefill_flops(cfg, 2) - 2 * head == \
        2 * 2 * (365_813_760 - head) + 2 * 4 * 2560 * 4
