"""Whole runs on the CPU at a tiny size, with the harness's look for a
chip skipped: a sound program comes out correct, and each fault a cell
can have, planted under the timed path, comes out not correct."""
import time

import jax
import jax.numpy as jnp
import pytest

from harness import serve, train
from harness.clock import CompileClock

import tiny

CLOCK = CompileClock()


def _run_train(cell, devices, **kw):
    return train.run(cell, devices, 2 ** 33 + 17, 0.5,
                     t_start=time.perf_counter(), clock=CLOCK, **kw)


def _correct(run):
    return all(v <= lim for _, v, lim in run["checks"])


def _worst_share(run):
    """The largest reading as a share of its limit."""
    return max(v / lim for _, v, lim in run["checks"] if lim)


@pytest.fixture(scope="module")
def sound_train():
    """A sound run at the test size.  Its bf16 rounding is a larger share
    of the tiny model's few-element leaves than at the cell's widths, so
    it is held to being well below every fault, not to the cell's
    limits."""
    return _run_train(tiny.cell("qwen4b-train-1chip", **tiny.TRAIN),
                      jax.devices()[:1])


def _frozen(step):
    """A step that returns its state unchanged."""
    def f(state, batch):
        return state, step(state, batch)[1]
    return f


def _half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def f(state, batch):
        half = jax.tree.map(lambda a: a[: a.shape[0] // 2], batch)
        return step(state, half)
    return f


@pytest.mark.parametrize("fault", [_frozen, _half_batch])
def test_training_fault_is_not_correct(fault, sound_train):
    run = _run_train(tiny.cell("qwen4b-train-1chip",
                               **dict(tiny.TRAIN, microbatches=1)),
                     jax.devices()[:1], wrap_step=fault)
    assert not _correct(run), run["checks"]
    assert _worst_share(run) > 3 * _worst_share(sound_train)


def test_pipeline_without_the_exchange_is_not_correct(monkeypatch):
    """The hop between stages left out: each stage receives zeros."""
    from repro.parallel import pipeline, wire
    monkeypatch.setattr(wire, "coded_ppermute",
                        lambda dtype, axis, perm, x: jnp.zeros_like(x))
    monkeypatch.setattr(pipeline.jax.lax, "ppermute",
                        lambda x, axis, perm: jnp.zeros_like(x))
    run = _run_train(tiny.cell("qwen4b-pipe4-int8", **tiny.PIPE),
                     jax.devices()[:4])
    assert not _correct(run), run["checks"]
    monkeypatch.undo()
    sound = _run_train(tiny.cell("qwen4b-pipe4-int8", **tiny.PIPE),
                       jax.devices()[:4])
    assert _worst_share(run) > 3 * _worst_share(sound)


def _run_serve(**kw):
    return serve.run(tiny.cell("qwen4b-serve-over-knee", **tiny.SERVE),
                     jax.devices()[:1], 2 ** 31 + 3, 2.0,
                     t_start=time.perf_counter(), clock=CLOCK, **kw)


def test_sound_serving_is_correct():
    run = _run_serve()
    assert run["checked_tokens"] > 0
    assert _correct(run), run["checks"]


def test_serving_with_an_altered_token_is_not_correct():
    run = _run_serve(alter_token=lambda t: (t + 1) % tiny.CONFIG[
        "vocab_size"])
    assert run["checked_tokens"] > 0
    assert not _correct(run), run["checks"]
