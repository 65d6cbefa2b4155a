"""The program's own measurement: the layer scopes in the compiled step
programs, the serving engine's spans on the profiler's clock, its lane
counter, and the compile counter.

A scope reaches each HLO instruction's ``metadata={op_name=...}``; a path
component may be wrapped by a transformation (``jvp(attention)``,
``transpose(jvp(attention))``), which ``_scopes`` unwraps.
"""
import glob
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.cache import CompileClock
from repro.models import LM, LMConfig
from repro.parallel.steps import make_lm_train_step
from repro.serving.engine import ServingEngine
from repro.serving.scheduler import Request
from repro.training.optim import adamw

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w.\-]*\((.*)\)$")

TRAIN_CFG = LMConfig(name="trace-train", num_layers=2, d_model=32,
                     n_heads=4, n_kv=2, d_ff=64, vocab=128, qkv_bias=True,
                     dtype="bfloat16")
SERVE_CFG = LMConfig(name="trace-serve", num_layers=2, d_model=32,
                     n_heads=2, n_kv=1, d_ff=32, vocab=64, dtype="float32")


def _scopes(op_name: str) -> set:
    out = set()
    for comp in op_name.split("/"):
        while (m := _WRAPPED.match(comp)):
            comp = m.group(1)
        out.add(comp)
    return out


def _backward(op_name: str) -> bool:
    return any(c.startswith("transpose(") for c in op_name.split("/"))


def _ops(text: str, opcode: str) -> list:
    """(result type, op_name) of every ``opcode`` instruction."""
    pat = re.compile(r"= (\S+) " + re.escape(opcode) + r"\(")
    out = []
    for line in text.splitlines():
        m = pat.search(line)
        if m:
            op = _OP_NAME.search(line)
            out.append((m.group(1).split("{")[0], op.group(1) if op else ""))
    return out


@pytest.fixture(scope="module")
def train_hlo():
    model, opt = LM(TRAIN_CFG), adamw(1e-3)
    params = jax.eval_shape(model.init, jax.random.key(0))
    state = jax.eval_shape(lambda p: {
        "params": p, "opt_state": opt.init(p),
        "step": jnp.zeros((), jnp.int32)}, params)
    tok = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    step = make_lm_train_step(model, opt, microbatches=2)
    return jax.jit(step).lower(state, {"tokens": tok, "labels": tok}) \
        .compile().as_text()


@pytest.mark.parametrize("scope", ["attention", "mlp", "head_loss"])
def test_train_step_carries_layer_scopes_forward_and_backward(train_hlo,
                                                              scope):
    names = [n for n in _OP_NAME.findall(train_hlo) if scope in _scopes(n)]
    assert any(not _backward(n) for n in names), f"no forward {scope} op"
    assert any(_backward(n) for n in names), f"no backward {scope} op"


@pytest.mark.parametrize("scope,backward", [("embed", True),
                                            ("grad_accum", False),
                                            ("optimizer", False)])
def test_train_step_names_the_rest_of_the_step(train_hlo, scope, backward):
    """The embedding, the sum over micro-batches and the optimizer
    update carry scopes of their own, so the layers' share of a step
    can be read against all of it."""
    names = [n for n in _OP_NAME.findall(train_hlo) if scope in _scopes(n)]
    assert any(not _backward(n) for n in names), f"no {scope} op"
    assert any(_backward(n) for n in names) == backward


def test_train_step_scopes_do_not_overlap(train_hlo):
    layers = {"attention", "mlp", "head_loss", "embed", "grad_accum",
              "optimizer"}
    for name in _OP_NAME.findall(train_hlo):
        assert len(_scopes(name) & layers) <= 1, name


def test_pipeline_hops_carry_their_scope():
    """Every collective-permute of the 4-stage pipeline step's tick loop
    (the int8 hop forward, its transpose backward) is under
    ``pipeline.hop``.  (The partitioner may add one outside the loop,
    a reshard of the step's inputs, which is not a hop.)"""
    code = textwrap.dedent("""
        import re
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_host_mesh
        from repro.models import LM, LMConfig
        from repro.parallel.pipeline import PipelineSpec
        from repro.parallel.sharding import ShardingPolicy
        from repro.parallel.steps import make_lm_train_step
        from repro.training.optim import adamw
        cfg = LMConfig(name="t", num_layers=4, d_model=32, n_heads=4,
                       n_kv=2, d_ff=64, vocab=128, dtype="bfloat16")
        model, opt = LM(cfg), adamw(1e-3)
        mesh = make_host_mesh(pod=4)
        spec = PipelineSpec(num_stages=4, microbatches=4,
                            wire_dtype="int8")
        shapes = jax.eval_shape(model.init, jax.random.key(0))
        state = jax.eval_shape(lambda p: {
            "params": p, "opt_state": opt.init(p),
            "step": jnp.zeros((), jnp.int32)}, shapes)
        sh = ShardingPolicy(mesh, pod_is_pipeline=True
                            ).train_state_shardings(state)
        state = jax.tree.map(lambda s, h: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=h), state, sh)
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        tok = jax.ShapeDtypeStruct((4, 16), jnp.int32, sharding=rep)
        step = make_lm_train_step(model, opt, pipeline=spec, mesh=mesh)
        text = jax.jit(step).lower(state, {"tokens": tok, "labels": tok}
                                   ).compile().as_text()
        for line in text.splitlines():
            if re.search(r" collective-permute(-start)?\\(", line):
                m = re.search(r'op_name="([^"]*)"', line)
                print("CP", m.group(1) if m else "")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    hops = [line[3:] for line in out.stdout.splitlines()
            if line.startswith("CP ") and "/while/" in line]
    assert len(hops) >= 2, out.stdout
    assert all("pipeline.hop" in _scopes(h) for h in hops), hops
    assert any(_backward(h) for h in hops), hops


@pytest.fixture(scope="module")
def model_params():
    model = LM(SERVE_CFG)
    return model, model.init(jax.random.key(0))


def _engine(model_params, slots=2, cache_len=16):
    model, params = model_params
    return ServingEngine(model, params, slots=slots, cache_len=cache_len)


def _requests(specs):
    rng = np.random.default_rng(5)
    return [Request(rid=i, prompt=rng.integers(0, SERVE_CFG.vocab, plen),
                    max_new_tokens=gen)
            for i, (plen, gen) in enumerate(specs)]


def _aliased_params(text: str) -> set:
    """Parameter numbers a compiled module's outputs alias (donation)."""
    head = text.split("\n", 1)[0]
    return {int(p) for p in re.findall(r"\}: \((\d+), \{\}", head)}


def _arena_params(eng, args_before) -> set:
    """Parameter numbers of the arena's leaves among the flattened
    arguments, which start with ``args_before`` (a pytree)."""
    first = len(jax.tree.leaves(args_before))
    return set(range(first, first + len(jax.tree.leaves(eng.cache))))


def test_decode_step_writes_the_arena_in_place_under_its_scope(
        model_params):
    """The decode step aliases every arena leaf from input to output,
    writes it one token per lane under the ``arena`` scope, and selects,
    copies or transposes nothing of an arena leaf's shape."""
    eng = _engine(model_params)
    args = (eng.params, eng.cache, jnp.asarray(eng.positions),
            jnp.asarray(eng.active), jnp.asarray(eng.tokens),
            jnp.asarray(eng.req_seed), jnp.asarray(eng.tok_idx))
    text = eng._step.lower(*args).compile().as_text()
    assert _arena_params(eng, eng.params) <= _aliased_params(text)
    arenas = {f"f32[{','.join(map(str, a.shape))}]"
              for a in jax.tree.leaves(eng.cache)}
    for opcode in ("select", "copy", "transpose"):
        moved = [op for shape, op in _ops(text, opcode) if shape in arenas]
        assert not moved, (opcode, moved)
    writes = [op for shape, op in _ops(text, "dynamic-update-slice")
              if shape in arenas]
    assert writes, f"no write into the arena's {arenas} leaves"
    assert all("arena" in _scopes(op) for op in writes), writes
    assert eng._step.lower(*args).as_text().startswith("module @jit_step")


def test_row_copies_run_under_the_arena_scope(model_params):
    eng = _engine(model_params)
    take = eng._take_row.lower(eng.cache, 0)
    put = eng._put_row.lower(eng.cache,
                             jax.eval_shape(eng._take_row, eng.cache, 0), 1)
    for lowered in (take, put):
        names = _OP_NAME.findall(lowered.compile().as_text())
        assert names and any("arena" in _scopes(n) for n in names)
    # the row write is donated the arena and writes it in place
    assert _arena_params(eng, ()) <= _aliased_params(
        put.compile().as_text())


def test_engine_spans_nest_in_a_profiler_trace(model_params, tmp_path):
    from jax.profiler import ProfileData
    eng = _engine(model_params)
    reqs = _requests([(3, 4), (5, 2), (4, 3)])
    eng.run(reqs[:1])                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(reqs[1:])
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    steps = spans["engine.step"]
    decodes, admits = eng.decode_steps, eng.prefill_chunks
    assert len(spans["engine.schedule"]) == len(steps)
    assert len(spans["engine.admit"]) == admits - 1     # one before
    for name in ("engine.decode", "engine.sync", "engine.emit"):
        assert len(spans[name]) == decodes - 4, name    # 4 before

    def inside(child, parents):
        return all(any(a <= x and y <= b for a, b in parents)
                   for x, y in child)
    for name in ("engine.schedule", "engine.admit", "engine.decode"):
        assert inside(spans[name], steps), name
    for name in ("engine.sync", "engine.emit"):
        assert inside(spans[name], spans["engine.decode"]), name


def test_lane_steps_count_the_active_lanes(model_params):
    eng = _engine(model_params, slots=3, cache_len=24)
    out = eng.run(_requests([(3, 5), (4, 2), (5, 7), (3, 1), (6, 4)]))
    stats = eng.stats()
    assert eng.lane_steps == sum(len(v) for v in out.values())
    assert stats["lane_steps"] == stats["qos"]["tokens_emitted"]
    assert stats["occupancy_mean"] == eng.lane_steps / eng.decode_steps
    assert 0 < stats["occupancy_mean"] <= eng.slots
    assert not hasattr(eng, "occupancy_trace")


def test_compile_clock_counts_programs_by_name():
    """A compile counts under its program's name; a load from the
    persistent cache (JAX's hit event inside the compile interval) counts
    as loaded, not compiled."""
    from repro.launch.cache import BACKEND_COMPILE, CACHE_HIT

    def clock_probe(x):
        return x * 3.0 + 1.0
    x = jnp.arange(5.0)
    clock = CompileClock()
    try:
        snap = clock.snapshot()
        jax.jit(clock_probe)(x).block_until_ready()
        jax.monitoring.record_event(CACHE_HIT)
        jax.monitoring.record_event_duration_secs(
            BACKEND_COMPILE, 0.25, fun_name="jit(clock_probe)")
        jax.monitoring.record_event_duration_secs(BACKEND_COMPILE, 0.5,
                                                  fun_name="other")
        seconds, compiled, loaded = clock.since(snap)
    finally:
        clock.close()
    assert compiled["jit(clock_probe)"] == 1
    assert loaded["jit(clock_probe)"] == 1
    assert compiled["other"] == 1 and loaded["other"] == 0
    assert seconds > 0.75
    assert clock.programs >= 3 and clock.cache_hits == 1
    jax.jit(lambda x: x - 1.0)(x)                # not counted after close
    assert clock.since(snap)[1] == compiled
