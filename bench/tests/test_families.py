"""A configuration of another model family costs a family file and no
edit to the harness: the harness finds ``<model_type>.py`` by the
configuration's ``model_type``.  ``fixtures/families/mixtral.py`` is a
Mixtral-shaped sparse-expert family that no cell uses; pointed at, it
drives a whole training run through the program's ``moe`` blocks and its
own plain reference."""
import dataclasses
import time

import jax
import pytest

from harness import metrics, spec, train, weights
from harness.clock import CompileClock
from harness.peaks import PEAKS

import tiny

#: a Mixtral-shaped configuration at a size a test can hold, in float32 so
#: that the comparison sees the family's structure and not bf16 rounding
MIXTRAL = {"model_type": "mixtral", "hidden_act": "silu", "hidden_size": 64,
           "intermediate_size": 96, "num_attention_heads": 4,
           "num_key_value_heads": 2, "num_hidden_layers": 2,
           "num_local_experts": 4, "num_experts_per_tok": 2,
           "vocab_size": 512, "rms_norm_eps": 1e-06, "rope_theta": 1e6,
           "tie_word_embeddings": False, "torch_dtype": "float32"}
SEED = 2 ** 34 + 5


@pytest.fixture
def moe_cell(monkeypatch):
    monkeypatch.setattr(spec, "FAMILIES_DIR", tiny.fixture("families"))
    return dataclasses.replace(
        tiny.cell("qwen4b-train-1chip", **tiny.TRAIN), config_name="tiny-moe",
        config=dict(MIXTRAL))


def _run(cell):
    return train.run(cell, jax.devices()[:1], SEED, 0.5,
                     t_start=time.perf_counter(), clock=CompileClock())


def _correct(run):
    return all(v <= lim for _, v, lim in run["checks"])


def test_family_is_found_by_model_type(moe_cell):
    fam = spec.family(moe_cell.config)
    assert fam.__name__ == "bench_family_mixtral"
    assert spec.family(dict(MIXTRAL)) is fam            # loaded once
    with pytest.raises(ValueError, match="no family file"):
        spec.family(dict(MIXTRAL, model_type="gpt_oss"))


def test_moe_family_runs_through_the_harness_and_is_correct(moe_cell):
    fam = spec.family(moe_cell.config)
    prog = train.program(moe_cell, jax.devices()[:1])
    got = {n: tuple(s.shape) for n, s in
           zip(weights.leaf_names(prog["shapes"]),
               jax.tree.leaves(prog["shapes"]))}
    ref = train.reference(moe_cell, SEED, jax.devices()[:1])
    assert got == fam.param_shapes(moe_cell.config) == {
        n: tuple(a.shape) for n, a in
        zip(weights.leaf_names(ref.params), jax.tree.leaves(ref.params))}
    assert "blocks/moe/router" in got
    run = _run(moe_cell)
    assert _correct(run), run["checks"]
    # the reader counts the family's work: its experts per token
    obs = metrics.Observation(cell=moe_cell, peaks=PEAKS["TPU v5 lite"],
                              chips=1, run=run, trace=None)
    per_token = fam.train_flops_per_token(MIXTRAL, tiny.TRAIN["seq"])
    assert per_token == 6.0 * (2 * (2 * 64 * 64 + 2 * 64 * 32 + 64 * 4
                                    + 2 * 3 * 64 * 96) + 64 * 512) \
        + 6.0 * 2 * 32 * 64
    assert metrics.read("train_mfu", obs) == pytest.approx(
        100 * per_token * run["train_tokens_per_s"] / 197e12)


def test_moe_family_without_its_kth_expert_is_not_correct(moe_cell,
                                                          monkeypatch):
    """The program's router leaves out each token's k-th expert (and
    renormalises the gates over the rest)."""
    from repro.models import moe
    local = moe._moe_local

    def without_kth(*args, topk, **kw):
        return local(*args, topk=topk - 1, **kw)
    monkeypatch.setattr(moe, "_moe_local", without_kth)
    run = _run(moe_cell)
    assert not _correct(run), run["checks"]
