"""Weights and token data from ``--seed``, made by the benchmark.

The program receives these arrays and the plain reference receives the
same ones, drawn again from the seed: the reference takes nothing the
program made.  Every leaf is drawn from its own key, and a stacked
block leaf ``[L, ...]`` draws layer ``l`` from ``fold_in(key, l)``, so
the reference can draw one layer at a time on the device that holds it
and get the values the program got in one call.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

#: Standard deviation of each leaf by its name.  Matmul weights keep
#: unit-variance activations (1/sqrt(fan_in)); the embedding and the head
#: follow the program's init (0.02, so a logit is N(0, 0.02^2 d) at init);
#: norm gains (applied as 1 + w) and q/k/v biases are drawn non-zero so
#: that the comparison sees them.
SMALL = {"w": 0.1, "qb": 0.1, "kb": 0.1, "vb": 0.1}
FIXED = {"embed": 0.02, "head": 0.02}


def seed_key(seed: int):
    """A JAX key from any whole number (seeds may pass 2**31)."""
    words = np.random.SeedSequence(int(seed) & (2 ** 64 - 1)
                                   ).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


def leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def leaf_std(name: str, shape) -> float:
    last = name.rsplit("/", 1)[-1]
    if last in FIXED:
        return FIXED[last]
    if last in SMALL:
        return SMALL[last]
    return 1.0 / math.sqrt(shape[-2])


def leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def draw_layer(key, name: str, shape, layer: int):
    """Layer ``layer`` of the stacked block leaf ``name`` (shape without
    the layer axis)."""
    k = jax.random.fold_in(leaf_key(key, name), layer)
    return jax.random.normal(k, shape, jnp.float32) * leaf_std(name, shape)


def draw_leaf(key, name: str, shape):
    if name.startswith("blocks/"):
        layers = jnp.arange(shape[0])
        return jax.vmap(lambda l: draw_layer(key, name, shape[1:], l))(layers)
    return jax.random.normal(leaf_key(key, name), shape,
                             jnp.float32) * leaf_std(name, shape)


def make_params(key, shapes):
    """The program's parameter tree (``shapes`` from ``eval_shape`` of its
    init), drawn from ``key``.  Call inside ``jax.jit``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, s: draw_leaf(key, leaf_name(path), s.shape), shapes)


def leaf_names(tree) -> list:
    return [leaf_name(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def token_rows(seed: int, rows: int, length: int, vocab: int,
               stream: int) -> np.ndarray:
    """``rows`` x ``length`` token ids in [0, vocab) from the seed; every
    ``stream`` gives different rows."""
    rng = np.random.default_rng([int(seed) & (2 ** 64 - 1), stream])
    return rng.integers(0, vocab, size=(rows, length), dtype=np.int32)
