"""Inputs from the seed: the same work for every seed, in another order,
and the same weights for the program and the reference."""
import collections

import jax
import numpy as np

from harness import reference, serve, weights

import tiny


def test_every_seed_offers_the_same_work():
    tr = tiny.cell("qwen4b-serve-over-knee").traffic
    runs = [serve.make_requests(tr, s, 40.0, 18992)
            for s in (1, 2 ** 33 + 1)]
    for a in runs:
        assert len(a) == round(tr["rate_per_s"] * 40.0)
        assert all(0 < r["due"] < 40.0 for r in a)
        assert sorted(r["due"] for r in a) == [r["due"] for r in a]
    keys = [collections.Counter((len(r["prompt"]), r["max_new"]) for r in a)
            for a in runs]
    lens = [sorted(len(r["prompt"]) for r in a) for a in runs]
    outs = [sorted(r["max_new"] for r in a) for a in runs]
    gaps = [sorted(np.round(np.diff([0.0] + [r["due"] for r in a]), 9))
            for a in runs]
    assert lens[0] == lens[1] and outs[0] == outs[1]
    assert np.allclose(gaps[0], gaps[1])
    assert keys[0] != keys[1]         # another order


def test_warm_shapes_cover_the_scheduler_chunks():
    tr = tiny.cell("qwen4b-serve-over-knee").traffic
    assert serve.warm_shapes(tr, 16) == [(1, 128), (2, 128), (1, 256),
                                         (1, 512), (1, 1024), (1, 2048)]


def test_reference_draws_the_programs_weights():
    from harness import spec
    from repro.models.lm import LM
    cfg = tiny.CONFIG
    shapes = jax.eval_shape(LM(spec.family(cfg).lm_config(cfg)).init,
                            jax.random.key(0))
    seed = 2 ** 40 + 9
    prog = jax.jit(lambda k: weights.make_params(k, shapes))(
        weights.seed_key(seed))
    ref = reference.Reference(cfg, seed, jax.devices()[:2])
    for l, layer in enumerate(ref.layers):
        np.testing.assert_array_equal(layer["mlp"]["w2"],
                                      prog["blocks"]["mlp"]["w2"][l])
        np.testing.assert_array_equal(layer["attn"]["kb"],
                                      prog["blocks"]["attn"]["kb"][l])
    np.testing.assert_array_equal(ref.top["head"], prog["head"])
    np.testing.assert_array_equal(ref.embed, prog["embed"])
