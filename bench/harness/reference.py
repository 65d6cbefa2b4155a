"""The plain reference: a Qwen2 decoder in straightforward ``jax.numpy``.

It follows the published description (RMSNorm, rotary embeddings on
half-split pairs, causal softmax attention with q/k/v biases, a SiLU-
gated MLP, an untied head, mean token cross-entropy) and imports nothing
of the program.  Two departures, both forced by sharing the program's
weights: a norm gain is stored as ``w`` and applied as ``1 + w``, and
the vocabulary is the configuration's slice.

``precision="f32"`` computes every product in float32 at the highest
matmul precision.  ``precision="fp8"`` is the control: every matmul
operand (weights, activations, attention scores and probabilities, and
every cotangent on the way back) is rounded to float8 with a per-tensor
scale, the step below the bfloat16 the configuration computes in.

Work is split so that it fits beside nothing else on the chip: one row
of the batch at a time, layer by layer, each layer on the device that
holds it (layer ``l`` of ``L`` on device ``l * len(devices) // L``), the
embedding on the first device and the norm and head on the last.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
E4M3, E5M2 = jnp.float8_e4m3fn, jnp.float8_e5m2


def _round8(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def fp8(x):
    """Round to e4m3 with a per-tensor scale; the cotangent is rounded to
    e5m2 with its own scale (the usual float8 training recipe)."""
    return _round8(x, E4M3)


fp8.defvjp(lambda x: (_round8(x, E4M3), None),
           lambda _, g: (_round8(g, E5M2),))


def _op(x, precision):
    return fp8(x) if precision == "fp8" else x


def mm(a, b, precision):
    return jnp.dot(_op(a, precision), _op(b, precision), precision=HIGHEST)


def rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def rope(x, theta):
    """x [S, H, hd]; rotates the pairs (i, i + hd/2) by position."""
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(p, x, cfg, precision):
    """One decoder layer on one row: x [S, D] -> [S, D]."""
    s, d = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // nh
    a = p["attn"]
    h = rmsnorm(x, p["ln1"]["w"], cfg["rms_norm_eps"])
    q = (mm(h, a["q"], precision) + a["qb"]).reshape(s, nh, hd)
    k = (mm(h, a["k"], precision) + a["kb"]).reshape(s, nkv, hd)
    v = (mm(h, a["v"], precision) + a["vb"]).reshape(s, nkv, hd)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    scores = jnp.einsum("shd,thd->hst", _op(q, precision), _op(k, precision),
                        precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("hst,thd->shd", _op(probs, precision),
                     _op(v, precision), precision=HIGHEST)
    x = x + mm(att.reshape(s, nh * hd), a["o"], precision)
    m = p["mlp"]
    h = rmsnorm(x, p["ln2"]["w"], cfg["rms_norm_eps"])
    g = jax.nn.silu(mm(h, m["w1"], precision)) * mm(h, m["w3"], precision)
    return x + mm(g, m["w2"], precision)


def logits(top, x, cfg, precision):
    h = rmsnorm(x, top["final_norm"]["w"], cfg["rms_norm_eps"])
    return mm(h, top["head"], precision)


def loss_sum(top, x, labels, cfg, precision):
    lg = logits(top, x, cfg, precision)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    return jnp.sum(lse - jnp.take_along_axis(lg, labels[:, None], -1)[:, 0])


# ---------------------------------------------------------------------------
# jitted pieces (cfg is hashed as a tuple of its items)
# ---------------------------------------------------------------------------


def _frozen(cfg: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_fwd(p, x, fcfg, precision):
    return layer(p, x, dict(fcfg), precision)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer_bwd(p, x, gy, fcfg, precision):
    _, vjp = jax.vjp(lambda p, x: layer(p, x, dict(fcfg), precision), p, x)
    return vjp(gy)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_grad(top, x, labels, fcfg, precision):
    return jax.value_and_grad(
        lambda t, x: loss_sum(t, x, labels, dict(fcfg), precision),
        argnums=(0, 1))(top, x)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _logits(top, x, fcfg, precision):
    return logits(top, x, dict(fcfg), precision)


@jax.jit
def _embed_grad(shape_like, tokens, gx):
    return jnp.zeros_like(shape_like).at[tokens].add(gx)


@jax.jit
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


@jax.jit
def _scale(a, c):
    return jax.tree.map(lambda x: x * c, a)


@jax.jit
def _zeros(tree):
    return jax.tree.map(jnp.zeros_like, tree)


@jax.jit
def _sq_norms(tree):
    return jax.tree.map(lambda x: jnp.sum(jnp.square(x)), tree)


@jax.jit
def _sq_diff_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.sum(jnp.square(x - y)), a, b)


@functools.partial(jax.jit, static_argnums=(5,))
def _adam(p, m, v, g, t, hp):
    lr, b1, b2, eps = hp
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    p = jax.tree.map(lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2)
                                                         + eps), p, m, v)
    return p, m, v


def param_shapes(cfg: dict) -> dict:
    """Leaf name -> shape of the decoder's parameters; block leaves carry
    the layer axis first."""
    d, f, v = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["vocab_size"])
    nl, hd = cfg["num_hidden_layers"], d // cfg["num_attention_heads"]
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    out = {"embed": (v, d), "final_norm/w": (d,), "head": (d, v)}
    for name, shape in (("attn/q", (d, nq)), ("attn/k", (d, nkv)),
                        ("attn/v", (d, nkv)), ("attn/o", (nq, d)),
                        ("attn/qb", (nq,)), ("attn/kb", (nkv,)),
                        ("attn/vb", (nkv,)), ("ln1/w", (d,)), ("ln2/w", (d,)),
                        ("mlp/w1", (d, f)), ("mlp/w3", (d, f)),
                        ("mlp/w2", (f, d))):
        out["blocks/" + name] = (nl,) + shape
    return out


@functools.lru_cache(maxsize=None)
def _layer_drawer(dev, leaves: tuple):
    """One jitted call per layer: every block leaf of layer ``l`` (a traced
    index), on ``dev``."""
    def draw(key, l):
        return {name: W.draw_layer(key, name, shape, l)
                for name, shape in leaves}
    return jax.jit(draw, out_shardings=jax.sharding.SingleDeviceSharding(dev))


@functools.lru_cache(maxsize=None)
def _leaf_drawer(dev, name: str, shape: tuple):
    return jax.jit(lambda key: W.draw_leaf(key, name, shape),
                   out_shardings=jax.sharding.SingleDeviceSharding(dev))


class Reference:
    """The reference model, its AdamW state and its steps.

    The values are drawn from ``seed`` here, leaf by leaf, as
    ``harness.weights`` draws the program's.  Options that
    make it stand in for a faulty program: ``precision="fp8"`` (the
    control), ``rows`` (train on a subset of the batch's rows) and
    ``drop_exchange`` (a layer on another device than the one before it
    receives zeros instead of its input).
    """

    def __init__(self, cfg: dict, seed: int, devices, *,
                 precision: str = "f32", rows=None,
                 drop_exchange: bool = False, adam=None):
        self.cfg, self.fcfg = cfg, _frozen(cfg)
        self.precision, self.rows = precision, rows
        self.drop_exchange = drop_exchange
        self.adam = adam
        self.devices = list(devices)
        self.n_layers = cfg["num_hidden_layers"]
        key = W.seed_key(seed)
        shapes = param_shapes(cfg)
        self.layers = []
        leaves = tuple((name, shape[1:]) for name, shape in shapes.items()
                       if name.startswith("blocks/"))
        for l in range(self.n_layers):
            tree = {}
            drawn = _layer_drawer(self.device_of(l), leaves)(key, l)
            for name, arr in drawn.items():
                _set(tree, name.split("/")[1:], arr)
            self.layers.append(tree)

        def top_leaf(name, dev):
            return _leaf_drawer(dev, name, shapes[name])(key)
        first, last = self.devices[0], self.devices[-1]
        self.embed = top_leaf("embed", first)
        self.top = {"final_norm": {"w": top_leaf("final_norm/w", last)},
                    "head": top_leaf("head", last)}
        self.initial = None
        self.state = None
        self.t = 0

    def device_of(self, l: int):
        return self.devices[l * len(self.devices) // self.n_layers]

    # -- forward ----------------------------------------------------------

    def _to(self, x, l: int):
        dev = self.device_of(l)
        if l > 0 and dev != self.device_of(l - 1) and self.drop_exchange:
            return jax.device_put(jnp.zeros(x.shape, x.dtype), dev)
        return jax.device_put(x, dev)

    def _forward_row(self, tokens):
        x = jax.device_put(self.embed[jnp.asarray(tokens)], self.device_of(0))
        xs = []
        for l, p in enumerate(self.layers):
            x = self._to(x, l)
            xs.append(x)
            x = _layer_fwd(p, x, self.fcfg, self.precision)
        return xs, jax.device_put(x, self.devices[-1])

    def logits(self, tokens):
        """Logits [S, V] of one row of tokens, on the last device."""
        _, x = self._forward_row(tokens)
        return _logits(self.top, x, self.fcfg, self.precision)

    # -- training ---------------------------------------------------------

    def _grads(self, batch):
        """Mean token loss of ``batch`` [B, S+1] and its gradient."""
        rows = batch if self.rows is None else batch[:self.rows]
        fwd = [self._forward_row(r[:-1]) for r in rows]
        total, g_layers, g_top, g_embed = 0.0, None, None, None
        for r, (xs, x) in zip(rows, fwd):
            labels = jax.device_put(jnp.asarray(r[1:]), self.devices[-1])
            loss, (gt, gx) = _head_grad(self.top, x, labels, self.fcfg,
                                        self.precision)
            total = total + loss
            g_top = gt if g_top is None else _add(g_top, gt)
            gl = [None] * self.n_layers
            for l in reversed(range(self.n_layers)):
                gx = jax.device_put(gx, self.device_of(l))
                gl[l], gx = _layer_bwd(self.layers[l], xs[l], gx, self.fcfg,
                                       self.precision)
                if (l > 0 and self.drop_exchange
                        and self.device_of(l) != self.device_of(l - 1)):
                    gx = jnp.zeros_like(gx)
            g_layers = gl if g_layers is None else [
                _add(a, b) for a, b in zip(g_layers, gl)]
            ge = _embed_grad(self.embed, jnp.asarray(r[:-1]),
                             jax.device_put(gx, self.devices[0]))
            g_embed = ge if g_embed is None else _add(g_embed, ge)
        n = float(len(rows) * (batch.shape[1] - 1))
        grads = {"layers": [_scale(g, 1 / n) for g in g_layers],
                 "top": _scale(g_top, 1 / n), "embed": _scale(g_embed, 1 / n)}
        return float(total) / n, grads

    def _params(self):
        return {"layers": self.layers, "top": self.top, "embed": self.embed}

    def _set_params(self, tree):
        self.layers, self.top, self.embed = (tree["layers"], tree["top"],
                                             tree["embed"])

    def train_step(self, batch):
        """One AdamW step on ``batch``; returns (loss, gradient tree)."""
        lr, b1, b2, eps = self.adam
        params = self._params()
        if self.state is None:
            self.initial = params
            zeros = {"top": _zeros(params["top"]),
                     "embed": _zeros(params["embed"]),
                     "layers": [_zeros(t) for t in params["layers"]]}
            self.state = (zeros, zeros)
        loss, grads = self._grads(batch)
        self.t += 1
        m, v = self.state
        new_p, new_m, new_v = {}, {}, {}
        for part in ("top", "embed"):
            new_p[part], new_m[part], new_v[part] = _adam(
                params[part], m[part], v[part], grads[part],
                float(self.t), (lr, b1, b2, eps))
        outs = [_adam(p, mm_, vv, g, float(self.t), (lr, b1, b2, eps))
                for p, mm_, vv, g in zip(params["layers"], m["layers"],
                                         v["layers"], grads["layers"])]
        new_p["layers"] = [o[0] for o in outs]
        new_m["layers"] = [o[1] for o in outs]
        new_v["layers"] = [o[2] for o in outs]
        self._set_params(new_p)
        self.state = (new_m, new_v)
        return loss, grads

    def leaf_norms(self, tree) -> dict:
        """Per-leaf L2 norms of a tree shaped like ``_params()``, named and
        stacked as the program's leaves are."""
        return _named_norms(jax.device_get(_tree_sq(tree)))

    def change_norms(self) -> dict:
        """Per-leaf norms of the parameters' change since the first step."""
        cur = self._params()
        sq = {"top": _sq_diff_norms(cur["top"], self.initial["top"]),
              "embed": _sq_diff_norms(cur["embed"], self.initial["embed"]),
              "layers": [_sq_diff_norms(a, b) for a, b in
                         zip(cur["layers"], self.initial["layers"])]}
        return _named_norms(jax.device_get(sq))


def _tree_sq(tree):
    return {"top": _sq_norms(tree["top"]), "embed": _sq_norms(tree["embed"]),
            "layers": [_sq_norms(t) for t in tree["layers"]]}


def _named_norms(sq) -> dict:
    out = {"embed": float(sq["embed"]),
           "final_norm/w": float(sq["top"]["final_norm"]["w"]),
           "head": float(sq["top"]["head"])}
    for layer_sq in sq["layers"]:
        for path, val in jax.tree_util.tree_flatten_with_path(layer_sq)[0]:
            name = "blocks/" + W.leaf_name(path)
            out[name] = out.get(name, 0.0) + float(val)
    return {k: math.sqrt(v) for k, v in out.items()}


def _set(tree: dict, keys, value):
    for k in keys[:-1]:
        tree = tree.setdefault(k, {})
    tree[keys[-1]] = value
