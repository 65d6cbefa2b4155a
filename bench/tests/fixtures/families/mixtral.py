"""Mixtral-shaped sparse-expert decoders (``model_type`` "mixtral"), as
a family file a test points the harness at: a family the benchmark has
no cell of, added without an edit to any harness file.

The model (Mixtral-8x7B, https://huggingface.co/mistralai/Mixtral-8x7B-v0.1/blob/main/config.json):
RMSNorm, rotary grouped-query attention without biases, and in place of
the MLP ``num_local_experts`` SiLU-gated experts, of which a softmax
router picks ``num_experts_per_tok`` per token by top-k and weighs them
by their gates renormalised to sum to 1; an untied head.

The program runs it on its ``moe`` blocks with ``moe_capacity`` set so
that an expert can take every token (``lm_config``): no token drops.
The plain float32 reference below departs from the published model
where the program does, each noted:

- a norm gain is stored as ``w`` and applied as ``1 + w``, at the
  program's eps (1e-6; Mixtral publishes 1e-5);
- the load-balance loss is the program's Switch aux,
  ``E * sum_e f_e p_e`` over one micro-batch's tokens, summed over the
  layers and added to the loss at 0.01 per micro-batch (Mixtral:
  ``router_aux_loss_coef`` 0.02, over the layers' mean, over the whole
  batch);
- every expert is evaluated on every token and weighed by its gate
  where the router picked it, by 0 elsewhere: plain, not sparse.

Training cells only: no ``logits`` and no serving counts.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness import reference as R
from harness import weights as W

#: the program's weight on the router's load-balance loss
AUX_WEIGHT = 0.01


def lm_config(config: dict, name: str = "bench"):
    from repro.models.config import LMConfig
    experts, topk = config["num_local_experts"], config["num_experts_per_tok"]
    return LMConfig(
        name=name, family="moe",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab=config["vocab_size"],
        act=config["hidden_act"], norm="rmsnorm",
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        moe_experts=experts, moe_topk=topk,
        # capacity = tokens * topk * factor / experts: every token
        moe_capacity=experts / topk,
        dtype=config["torch_dtype"])


def param_shapes(config: dict) -> dict:
    d, f, v = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    nl, e = config["num_hidden_layers"], config["num_local_experts"]
    hd = d // config["num_attention_heads"]
    nq, nkv = config["num_attention_heads"] * hd, \
        config["num_key_value_heads"] * hd
    out = {"embed": (v, d), "final_norm/w": (d,), "head": (d, v)}
    for name, shape in (("attn/q", (d, nq)), ("attn/k", (d, nkv)),
                        ("attn/v", (d, nkv)), ("attn/o", (nq, d)),
                        ("ln1/w", (d,)), ("ln2/w", (d,)),
                        ("moe/router", (d, e)), ("moe/w1", (e, d, f)),
                        ("moe/w3", (e, d, f)), ("moe/w2", (e, f, d))):
        out["blocks/" + name] = (nl,) + shape
    return out


def train_flops_per_token(config: dict, seq: int) -> float:
    """6 per weight a token multiplies (its ``num_experts_per_tok``
    experts, the router, attention's projections, the head) plus causal
    attention's 6 * layers * seq * d_model, as ``harness/flops.py``."""
    d, f = config["hidden_size"], config["intermediate_size"]
    hd = d // config["num_attention_heads"]
    q = config["num_attention_heads"] * hd
    kv = config["num_key_value_heads"] * hd
    per_layer = (2 * d * q + 2 * d * kv + d * config["num_local_experts"]
                 + config["num_experts_per_tok"] * 3 * d * f)
    nl = config["num_hidden_layers"]
    return 6.0 * (nl * per_layer + d * config["vocab_size"]) \
        + 6.0 * nl * seq * d


def published(config: dict) -> dict:
    """Mixtral-8x7B's published sizes."""
    return {"hidden_size": 4096, "intermediate_size": 14336,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "num_hidden_layers": 32, "vocab_size": 32000,
            "num_local_experts": 8, "num_experts_per_tok": 2,
            "tie_word_embeddings": False}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _op(x, precision):
    return R.fp8(x) if precision == "fp8" else x


def _einsum(spec, a, b, precision):
    return jnp.einsum(spec, _op(a, precision), _op(b, precision),
                      precision=R.HIGHEST)


def _attention(p, x, cfg, precision):
    """x + attention of one row x [S, D]."""
    s, d = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // nh
    h = R.rmsnorm(x, p["ln1"]["w"], 1e-6)
    q = R.mm(h, p["attn"]["q"], precision).reshape(s, nh, hd)
    k = R.mm(h, p["attn"]["k"], precision).reshape(s, nkv, hd)
    v = R.mm(h, p["attn"]["v"], precision).reshape(s, nkv, hd)
    q, k = R.rope(q, cfg["rope_theta"]), R.rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    scores = _einsum("shd,thd->hst", q, k, precision) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = _einsum("hst,thd->shd", probs, v, precision)
    return x + R.mm(att.reshape(s, nh * hd), p["attn"]["o"], precision)


def _experts(m, h, cfg, precision):
    """The experts' sum over tokens h [T, D], and the Switch aux."""
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(R.mm(h, m["router"], precision), axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    picked = jax.nn.one_hot(idx, e)                          # [T, K, E]
    weight = jnp.sum(picked * gate[..., None], axis=1)       # [T, E]
    a = jax.nn.silu(_einsum("td,edf->etf", h, m["w1"], precision)) \
        * _einsum("td,edf->etf", h, m["w3"], precision)
    y = _einsum("etf,efd->etd", a, m["w2"], precision)
    out = jnp.einsum("te,etd->td", weight, y, precision=R.HIGHEST)
    aux = e * jnp.sum(jnp.mean(probs, 0) * jnp.mean(picked.sum(1), 0) / k)
    return out, aux


def _loss(params, tokens, labels, fcfg, precision):
    """Mean token loss of one micro-batch [R, S] plus its weighed aux."""
    cfg = dict(fcfg)
    r, s = tokens.shape
    x = params["embed"][tokens]
    aux = 0.0
    for layer in range(params["blocks"]["ln1"]["w"].shape[0]):
        p = jax.tree.map(lambda a: a[layer], params["blocks"])
        x = jax.vmap(lambda row: _attention(p, row, cfg, precision))(x)
        h = R.rmsnorm(x, p["ln2"]["w"], 1e-6).reshape(r * s, -1)
        out, a = _experts(p["moe"], h, cfg, precision)
        x = x + out.reshape(x.shape)
        aux = aux + a
    h = R.rmsnorm(x, params["final_norm"]["w"], 1e-6)
    lg = R.mm(h, params["head"], precision)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    xent = jnp.mean(lse - jnp.take_along_axis(lg, labels[..., None], -1)[..., 0])
    return xent + AUX_WEIGHT * aux


_grad = jax.jit(jax.value_and_grad(_loss), static_argnums=(3, 4))
_KEYS = ("num_attention_heads", "num_key_value_heads", "rope_theta",
         "num_local_experts", "num_experts_per_tok")


def _tree(names_to_shapes: dict):
    tree = {}
    for name, shape in names_to_shapes.items():
        *path, last = name.split("/")
        node = tree
        for k in path:
            node = node.setdefault(k, {})
        node[last] = jax.ShapeDtypeStruct(shape, jnp.float32)
    return tree


def _norms(tree) -> dict:
    sq = jax.tree.map(lambda x: float(jnp.sum(jnp.square(x))), tree)
    return {n: math.sqrt(v) for n, v in
            zip(W.leaf_names(sq), jax.tree.leaves(sq))}


class Reference:
    """The reference model, drawn from ``seed`` as ``harness.weights``
    draws the program's, with its AdamW state, on ``devices[0]``.
    ``precision="fp8"`` is the control; ``rows`` trains on the batch's
    first rows only."""

    def __init__(self, config: dict, seed: int, devices, *, adam=None,
                 precision: str = "f32", microbatches: int = 1, rows=None):
        self.fcfg = tuple((k, config[k]) for k in _KEYS)
        self.adam, self.precision = adam, precision
        self.microbatches, self.rows = microbatches, rows
        shapes = _tree(param_shapes(config))
        self.params = jax.jit(
            lambda key: W.make_params(key, shapes),
            out_shardings=jax.sharding.SingleDeviceSharding(devices[0]))(
            W.seed_key(seed))
        self.initial = self.params
        self.m = self.v = jax.tree.map(jnp.zeros_like, self.params)
        self.t = 0

    def train_step(self, batch):
        """One AdamW step on ``batch`` [B, S+1]; returns (loss, gradient):
        the mean over its micro-batches, as the program splits them."""
        rows = np.asarray(batch if self.rows is None else batch[:self.rows])
        losses, grads = [], []
        for mb in np.split(rows, self.microbatches):
            loss, g = _grad(self.params, jnp.asarray(mb[:, :-1]),
                            jnp.asarray(mb[:, 1:]), self.fcfg,
                            self.precision)
            losses.append(float(loss))
            grads.append(g)
        grad = jax.tree.map(lambda *g: sum(g) / len(g), *grads)
        lr, b1, b2, eps = self.adam
        self.t += 1
        self.m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, self.m, grad)
        self.v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                              self.v, grad)
        c1, c2 = 1 - b1 ** self.t, 1 - b2 ** self.t
        self.params = jax.tree.map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
            self.params, self.m, self.v)
        return sum(losses) / len(losses), grad

    def leaf_norms(self, tree) -> dict:
        return _norms(tree)

    def change_norms(self) -> dict:
        return _norms(jax.tree.map(jnp.subtract, self.params, self.initial))
