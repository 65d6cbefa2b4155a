"""Continuous-batching serving engine: one jitted fixed-shape decode.

The engine owns a ``kv`` slot arena of ``slots`` lanes and runs ONE
jitted decode-plus-sample program per step regardless of which requests
occupy which slots:

* the model's batched ``decode_step`` runs once over the whole arena
  with a per-lane position vector: each lane ropes, masks and writes at
  its own position (and a MoE routes each lane's token alone),
  bit-exact vs a solo batch-1 decode for the f32 stacks the equivalence
  tests pin (global KV, local ring, rwkv, MoE);
* the arena is donated to the step and to the row write of an
  admission: a decode step writes one token's K and V per lane per layer
  into it in place, and nothing else of it (``arena_inplace`` counts the
  programs that consumed the arena they were given);
* ``attention_paths`` counts the path each attention call the engine's
  programs traced took (``models/common.py``): prefill's causal
  self-attention takes the blocked kernel on a TPU, decode never;
* temperature sampling runs INSIDE the jit with per-request keys
  (``fold_in(fold_in(key(seed), rid), token_index)``) — reproducible
  and independent of slot assignment and batch composition;
* inactive lanes are held: their tokens, positions and recurrent states
  stay as they were; their K/V token lands at their held position in
  their own slot, which is never read before it is written again.

Prefill is chunked through the scheduler: one length-bucketed chunk
(``LM.prefill_with_cache`` at the bucket's exact prompt length — no
padding, bit-identical to each request's solo prefill; a MoE's prompts
each alone, under a vmap, so no expert's capacity is shared) is interleaved
with decode steps under the chunk token budget, so long prompt bursts
do not stall in-flight decodes.

Modeled cost accounting (the deterministic CI metric): a decode step
bills ``slots`` lane-tokens (the fixed-shape program computes every
lane), a prefill chunk bills its exact token count.  The
run-to-completion convoy baseline bills ``batch * max_gen`` per group —
``convoy_units`` prices it for the same request set, which is what
``benchmarks/serve_bench.py`` gates the >= 1.5x win on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.common import AttentionPaths
from repro.runtime.qos import ServingQoS
from repro.serving import kv
from repro.serving.scheduler import Request, Scheduler


def _arena(fn):
    """``fn`` with its operations under the ``arena`` scope: the slot
    arena's row copies, told apart from the model in a device trace.  The
    jitted program keeps ``fn``'s name."""
    @functools.wraps(fn)
    def scoped(*args):
        with jax.named_scope("arena"):
            return fn(*args)
    return scoped


def _check_servable(cfg):
    if getattr(cfg, "enc_layers", 0) or cfg.family in ("audio", "vlm"):
        raise ValueError(
            f"continuous batching serves decoder-only token LMs; "
            f"{cfg.name} (family={cfg.family}) carries encoder state "
            "the slot arena does not manage")


def make_sample_step(model, temperature: float):
    """decode + sample fused into ONE jitted program (the static serve
    path's per-token step — sampling used to run un-jitted on
    host-synced logits each token).

    ``step(params, serve_state, tok, key) -> (next_tok, logits,
    serve_state, key)``.  Greedy (``temperature == 0``) is a traced
    argmax; temperature sampling splits the carried key inside the jit
    exactly like the old host loop did, so both paths are bit-identical
    to the pre-fusion behaviour.
    """
    from repro.parallel.steps import make_decode_step
    decode = make_decode_step(model)
    temperature = float(temperature)

    def step(params, serve_state, tok, key):
        logits, serve_state = decode(params, serve_state, tok)
        if temperature > 0:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(
                sub, logits / temperature, axis=-1)[:, None]
            nxt = nxt.astype(jnp.int32)
        else:
            nxt = jnp.argmax(logits, axis=-1,
                             keepdims=True).astype(jnp.int32)
        return nxt, logits, serve_state, key

    return jax.jit(step)


class ServingEngine:
    """Slot-based continuous batching over one decoder-only LM."""

    def __init__(self, model, params, *, slots: int, cache_len: int,
                 temperature: float = 0.0, seed: int = 0,
                 prefill_chunk_tokens: int = 256, policy: str = "fifo",
                 max_queue: int | None = None, cache_dtype=jnp.float32,
                 qos: ServingQoS | None = None):
        _check_servable(model.cfg)
        if slots < 1:
            raise ValueError(f"slots={slots} must be >= 1")
        self.model = model
        self.params = params
        self.slots = int(slots)
        self.cache_len = int(cache_len)
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.cache_dtype = cache_dtype
        self.qos = qos or ServingQoS()
        self.axes = kv.slot_axes(model, self.cache_len, cache_dtype)
        self.scheduler = Scheduler(
            cache_len=self.cache_len,
            prefill_chunk_tokens=prefill_chunk_tokens,
            policy=policy, max_queue=max_queue)
        self.freelist = kv.FreeList(self.slots)

        # device arena + host-side lane registers
        self.cache = model.init_cache(self.slots, self.cache_len,
                                      cache_dtype)
        self.positions = np.zeros(self.slots, np.int32)
        self.active = np.zeros(self.slots, bool)
        self.tokens = np.zeros(self.slots, np.int32)
        self.req_seed = np.zeros(self.slots, np.int32)
        self.tok_idx = np.zeros(self.slots, np.int32)
        self._tenant: dict[int, Request] = {}     # slot -> request
        self.outputs: dict[int, list] = {}        # rid -> emitted tokens
        self.done: dict[int, np.ndarray] = {}

        # accounting
        self.decode_steps = 0
        self.prefill_chunks = 0
        self.engine_units = 0                     # modeled lane-tokens
        self.lane_steps = 0                       # active lanes, summed

        self.arena_inplace = 0                    # arena programs in place
        self.attention_paths = AttentionPaths()   # traced calls, by path

        # the arena (argument 1 of the step, 0 of the row write) is
        # donated: each program writes into it and returns it
        self._step = jax.jit(self._build_step(), donate_argnums=1)
        self._prefill = jax.jit(self._prefill_bucket)
        self._take_row = jax.jit(_arena(
            lambda tree, i: kv.take_slot(tree, self.axes, i)))
        self._put_row = jax.jit(_arena(
            lambda tree, row, s: kv.put_slot(tree, self.axes, row, s)),
            donate_argnums=0)

    # -- jitted programs -----------------------------------------------------

    def _build_step(self):
        model = self.model
        temperature, seed = self.temperature, self.seed

        def sample(logits, rs, ti):
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.key(seed), rs), ti)
            return jax.random.categorical(key, logits / temperature,
                                          axis=-1)

        def step(params, cache, positions, active, tokens, req_seed,
                 tok_idx):
            logits, cache = model.decode_step(
                params, tokens[:, None], cache, positions, active=active)
            if temperature > 0:
                nxt = jax.vmap(sample)(logits, req_seed, tok_idx)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            nxt = jnp.where(active, nxt.astype(jnp.int32), tokens)
            return cache, nxt

        return step

    def _prefill_bucket(self, params, tokens):
        """Bucket prefill + greedy seed token (argmax of the prefill
        logits — fed to the first decode, never emitted, matching the
        static serve path).

        A MoE stack prefills each prompt alone (a vmap of batch-1
        prefills): its expert capacity counts every token dispatched
        together, so one batched prefill could drop a prompt's tokens
        for its neighbours'."""
        model, axes = self.model, self.axes

        def prefill(t):
            logits, serve_state = model.prefill_with_cache(
                params, {"tokens": t}, cache_len=self.cache_len,
                cache_dtype=self.cache_dtype)
            return logits, serve_state["cache"]

        if model.cfg.is_moe:
            def one(t):
                logits, cache = prefill(t[None])
                return logits[0], kv.take_slot(cache, axes, 0)

            logits, cache = jax.vmap(one, out_axes=(0, axes))(tokens)
        else:
            logits, cache = prefill(tokens)
        tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return cache, tok0

    # -- request intake ------------------------------------------------------

    def submit(self, req: Request) -> bool:
        self.qos.record_submit(req.rid)
        ok = self.scheduler.submit(req)
        if not ok:
            self.qos.record_reject(req.rid)
        return ok

    # -- engine iterations ---------------------------------------------------

    def _admit_chunk(self, chunk: list) -> None:
        prompts = jnp.asarray(np.stack([r.prompt for r in chunk]),
                              jnp.int32)
        with self.attention_paths:
            bucket_cache, tok0 = self._prefill(self.params, prompts)
        tok0 = np.asarray(tok0)
        self.prefill_chunks += 1
        self.engine_units += int(prompts.size)
        for i, req in enumerate(chunk):
            slot = self.freelist.alloc()
            row = self._take_row(bucket_cache, i)
            old = jax.tree.leaves(self.cache)
            self.cache = self._put_row(self.cache, row, slot)
            self._count_inplace(old)
            self.positions[slot] = req.prompt_len
            self.active[slot] = True
            self.tokens[slot] = tok0[i]
            self.req_seed[slot] = req.rid
            self.tok_idx[slot] = 0
            self._tenant[slot] = req
            self.outputs[req.rid] = []
            self.qos.record_admit(req.rid, self.decode_steps)

    def _count_inplace(self, old) -> None:
        """Count an arena program in ``arena_inplace`` when it consumed
        every arena leaf ``old`` it was given: it wrote into the donated
        buffers, not into a copy."""
        self.arena_inplace += all(a.is_deleted() for a in old)

    def _decode_once(self) -> None:
        old = jax.tree.leaves(self.cache)
        self.cache, nxt = self._step(
            self.params, self.cache, jnp.asarray(self.positions),
            jnp.asarray(self.active), jnp.asarray(self.tokens),
            jnp.asarray(self.req_seed), jnp.asarray(self.tok_idx))
        self._count_inplace(old)
        with TraceAnnotation("engine.sync"):
            nxt = np.asarray(nxt)
        self.decode_steps += 1
        self.engine_units += self.slots
        self.lane_steps += int(self.active.sum())
        with TraceAnnotation("engine.emit"):
            self._emit(nxt)

    def _emit(self, nxt: np.ndarray) -> None:
        """Hand each active lane's token to its request; free the slots
        of requests that reached their length."""
        finished = []
        for slot, req in self._tenant.items():
            if not self.active[slot]:
                continue
            self.outputs[req.rid].append(int(nxt[slot]))
            self.qos.record_token(req.rid, self.decode_steps)
            self.positions[slot] += 1
            self.tok_idx[slot] += 1
            self.tokens[slot] = nxt[slot]
            if len(self.outputs[req.rid]) >= req.max_new_tokens:
                finished.append(slot)
        for slot in finished:
            req = self._tenant.pop(slot)
            self.active[slot] = False
            self.freelist.free(slot)
            self.done[req.rid] = np.asarray(self.outputs[req.rid],
                                            np.int32)
            self.qos.record_done(req.rid, self.decode_steps)

    def step_once(self) -> bool:
        """One engine iteration: at most one prefill chunk, then one
        decode step.  Returns False when fully idle.

        The host spans ``engine.step`` (all of it), ``engine.schedule``,
        ``engine.admit`` and ``engine.decode`` (with ``engine.sync`` and
        ``engine.emit`` inside) land in a profiler trace on the device's
        clock; with no profiler running each is one cheap TraceMe."""
        with TraceAnnotation("engine.step"):
            with TraceAnnotation("engine.schedule"):
                chunk = self.scheduler.next_chunk(len(self.freelist))
            if chunk:
                with TraceAnnotation("engine.admit"):
                    self._admit_chunk(chunk)
            if self.active.any():
                with TraceAnnotation("engine.decode"):
                    self._decode_once()
                return True
            return bool(chunk)

    def run(self, requests=None, max_steps: int | None = None) -> dict:
        """Drain: submit ``requests`` (optional), iterate until idle.
        Returns ``{rid: np.ndarray of emitted tokens}``."""
        for req in (requests or []):
            self.submit(req)
        guard = max_steps if max_steps is not None else 10_000_000
        while (len(self.scheduler) or self.active.any()) and guard > 0:
            if not self.step_once():
                break
            guard -= 1
        if guard <= 0:
            raise RuntimeError(f"engine did not drain in {max_steps} steps")
        return dict(self.done)

    def stats(self) -> dict:
        return {
            "decode_steps": self.decode_steps,
            "prefill_chunks": self.prefill_chunks,
            "engine_units": self.engine_units,
            "lane_steps": self.lane_steps,
            "arena_inplace": self.arena_inplace,
            "attention_paths": dict(self.attention_paths),
            "occupancy_mean": (self.lane_steps / self.decode_steps
                               if self.decode_steps else 0.0),
            "qos": self.qos.snapshot(),
        }


# ---------------------------------------------------------------------------
# References: the solo decode the equivalence tests compare against, and
# the convoy cost model the bench gates the speedup on.
# ---------------------------------------------------------------------------


def solo_decode(model, params, prompt, max_new_tokens: int, *,
                cache_len: int, temperature: float = 0.0, seed: int = 0,
                rid: int = 0, cache_dtype=jnp.float32) -> np.ndarray:
    """Batch-1 run-to-completion decode with the ENGINE's sampling
    contract (greedy seed from the prefill logits; per-request
    ``fold_in`` keys at temperature > 0) — the ground truth every
    continuously-batched request must match bit-for-bit."""
    from repro.parallel.steps import make_decode_step
    decode = jax.jit(make_decode_step(model))
    prompt = jnp.asarray(np.asarray(prompt, np.int32).reshape(1, -1))
    logits, state = jax.jit(
        model.prefill_with_cache,
        static_argnames=("cache_len", "cache_dtype"))(
            params, {"tokens": prompt}, cache_len=cache_len,
            cache_dtype=cache_dtype)
    tok = jnp.argmax(logits, axis=-1, keepdims=True).astype(jnp.int32)
    out = []
    for i in range(max_new_tokens):
        logits, state = decode(params, state, tok)
        if temperature > 0:
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.key(seed), rid), i)
            nxt = jax.random.categorical(
                key, logits[0] / temperature, axis=-1)
        else:
            nxt = jnp.argmax(logits[0], axis=-1)
        tok = nxt.astype(jnp.int32)[None, None]
        out.append(int(tok[0, 0]))
    return np.asarray(out, np.int32)


def convoy_units(requests, batch: int) -> int:
    """Modeled lane-token cost of the static run-to-completion baseline:
    groups of ``batch`` in submission order; each group prefills its
    exact prompt tokens, then decodes ``batch * max(gen in group)``
    lane-tokens — everyone waits for the longest generation (the convoy
    tax continuous batching removes)."""
    reqs = list(requests)
    total = 0
    for i in range(0, len(reqs), batch):
        group = reqs[i:i + batch]
        total += sum(r.prompt_len for r in group)
        total += batch * max(r.max_new_tokens for r in group)
    return total
