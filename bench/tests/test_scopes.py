"""Device time by the program's scopes and spans (``harness/scopes.py``).

``fixtures/scopes.xplane.pb`` is a trace recorded on a TPU v5e by
``fixtures/record_scopes.py``: a jitted toy program whose two matmuls sit
under the scopes ``attention`` and ``mlp``, run three times inside the
serving engine's span names; ``fixtures/scopes.hlo.txt`` is that
program's compiled HLO.  The readers themselves are checked on the tiny
cells' real programs, compiled here, with one operation per instruction
standing in for a trace."""
import re

import jax
import pytest

from harness import metrics, scopes, trace

import tiny

TOY = re.compile(r"^jit_toy\b")
#: what ``record_scopes.py`` slept in its spans, with the device idle
SCHEDULE_S, EMIT_S, STEPS = 0.002, 0.001, 3


@pytest.fixture(scope="module")
def recorded():
    summary = trace.reduce_xplane(tiny.fixture("scopes.xplane.pb"),
                                  spans=trace.SPANS + scopes.ENGINE_SPANS)
    with open(tiny.fixture("scopes.hlo.txt")) as f:
        return summary, f.read()


def test_recorded_scopes_split_the_program(recorded):
    summary, hlo = recorded
    table = scopes.scope_table(hlo)
    assert {"attention", "mlp"} <= set(table.values())
    att = scopes.scope_seconds(summary, hlo, "attention", TOY)
    mlp = scopes.scope_seconds(summary, hlo, "mlp", TOY)
    assert att > 0 and mlp > 0
    split = scopes.by_scope(summary, hlo, TOY)
    assert split["total"] == pytest.approx(sum(split["seconds"].values()))
    assert split["seconds"]["attention"] == pytest.approx(att)
    assert split["unknown_share"] == 0.0
    assert scopes.module_runs(summary, TOY) == STEPS
    # every operation of the window ran inside the toy program
    busy = trace.op_seconds(summary, lambda n: True)
    assert split["total"] == pytest.approx(busy, rel=1e-6)


def test_recorded_readings_are_todays(recorded):
    """Over ``SCOPES`` the table reads what it read before a reader could
    name a scope of its own."""
    summary, hlo = recorded
    assert scopes.scope_seconds(summary, hlo, "attention", TOY) == 4.4316e-05
    assert scopes.scope_seconds(summary, hlo, "mlp", TOY) == 3.5558e-05
    assert scopes.by_scope(summary, hlo, TOY)["seconds"] == {
        None: 4.9e-08, "attention": 4.4316e-05, "mlp": 3.5558e-05}


def test_recorded_nested_scope_reads_its_own_time(recorded):
    """A scope outside ``SCOPES``, nested in ``mlp`` (the recorded HLO
    with the toy's second matmul put under ``mlp/mlp.matmul``), reads
    its own operations' time; ``mlp`` still reads the whole scope."""
    summary, hlo = recorded
    nested = hlo.replace('op_name="jit(toy)/mlp/dot_general"',
                         'op_name="jit(toy)/mlp/mlp.matmul/dot_general"')
    assert nested != hlo
    assert scopes.scope_seconds(summary, nested, "mlp.matmul", TOY) == \
        3.5558e-05
    assert scopes.scope_seconds(summary, nested, "mlp", TOY) == 3.5558e-05
    assert scopes.scope_seconds(summary, nested, "attention", TOY) == \
        4.4316e-05
    inner = scopes.by_scope(summary, nested, TOY,
                            names=scopes.with_scope("mlp.matmul"))
    assert inner["seconds"]["mlp.matmul"] == 3.5558e-05
    assert inner["seconds"].get("mlp", 0.0) == 0.0      # innermost wins
    assert scopes.scope_seconds(summary, hlo, "mlp.matmul", TOY) == 0.0


def test_recorded_engine_spans_and_idle_within(recorded):
    summary, _ = recorded
    names = [n for n, _, _ in summary.host]
    for name in set(scopes.ENGINE_SPANS) - {"engine.admit"}:
        assert names.count(name) == STEPS, name
    idle = {n: scopes.idle_within(summary, n)
            for n in scopes.ENGINE_SPANS + ("step_once",)}
    assert idle["engine.schedule"] >= STEPS * SCHEDULE_S
    assert idle["engine.emit"] >= STEPS * EMIT_S
    assert idle["engine.sync"] < idle["engine.schedule"]
    parts = idle["engine.schedule"] + idle["engine.decode"]
    assert parts <= idle["engine.step"] + 1e-12
    assert idle["engine.step"] <= idle["step_once"] + 1e-12
    assert idle["engine.decode"] >= idle["engine.sync"] + idle["engine.emit"]


def test_engine_spans_stay_out_of_the_benchmarks_host_spans():
    """Reduced as the benchmark reduces it, the same trace keeps only the
    benchmark's spans, so ``breakdown`` labels idle time as before."""
    plain = trace.reduce_xplane(tiny.fixture("scopes.xplane.pb"))
    assert {n for n, _, _ in plain.host} <= set(trace.SPANS)
    assert {g for g, _ in trace.breakdown(plain)["idle_gaps"]} <= {
        "step_once", "host"}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/jvp(attention)/dot_general", "attention"),
    ("jit(f)/transpose(jvp(jvp()))/checkpoint/rematted_computation/mlp/cos",
     "mlp"),
    ("jit(train_step)/transpose(jvp(head_loss))/convert_element_type",
     "head_loss"),
    ("jit(train_step)/transpose(jvp())/shard_map/while/body/closed_call/"
     "pipeline.hop/ppermute", "pipeline.hop"),
    ("jit(step)/arena/jit(_where)/select_n", "arena"),
    ("jit(f)/attention/arena/add", "arena"),
    ("state_tree['params']['blocks']['mlp']['w1']", None),
    ("jit(f)/jvp()/dynamic_slice", None),
])
def test_scope_of_unwraps_transformations(op_name, scope):
    assert scopes.scope_of(op_name) == scope


@pytest.mark.parametrize("op_name,scope,want,over_scopes", [
    ("jit(f)/transpose(jvp(mlp))/moe.experts/dot_general", "moe.experts",
     "moe.experts", "mlp"),
    ("jit(f)/mlp/router/dot_general", "moe.experts", "mlp", "mlp"),
    ("jit(f)/moe.experts/attention/add", "moe.experts", "attention",
     "attention"),
    ("jit(f)/jvp(moe.experts)/add", "moe.experts", "moe.experts", None),
])
def test_scope_of_a_name_outside_scopes(op_name, scope, want, over_scopes):
    assert scopes.scope_of(op_name, scopes.with_scope(scope)) == want
    assert scopes.scope_of(op_name) == over_scopes


def test_op_names_give_a_fusion_its_root_scope():
    hlo = """HloModule jit_f

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %a = f32[4]{0} tanh(%p), metadata={op_name="jit(f)/attention/tanh"}
  ROOT %m = f32[4]{0} multiply(%a, %a), metadata={op_name="jit(f)/mlp/mul"}
}

ENTRY %main.1 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  ROOT %fusion.3 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
}
"""
    table = scopes.scope_table(hlo)
    assert table["fusion.3"] == "mlp" and table["a"] == "attention"
    assert scopes.lookup(table, "fusion.3 fusion") == "mlp"
    assert scopes.lookup(table, "fusion.3.remat_uncompressed copy") == "mlp"
    assert scopes.lookup(table, "fusion.4 fusion") is None


def _stand_in_trace(hlo: str, module: str, runs: int) -> trace.Summary:
    """One operation of 1 us per instruction of ``hlo`` in each run of
    ``module``, back to back, inside the benchmark's window."""
    names = list(scopes.op_names(hlo))
    ops, mods, t = [], [], 0
    for _ in range(runs):
        mods.append([f"{module}(1)", t, 1000 * len(names)])
        for n in names:
            ops.append([f"{n} op", t, 1000])
            t += 1000
    return trace.Summary(devices=[{"id": 0, "ops": ops, "loops": [],
                                   "modules": mods}],
                         host=[[trace.WINDOW, 0, t]])


def _obs(cell, summary, **run):
    return metrics.Observation(cell=cell, peaks={}, chips=1, run=run,
                               trace=summary)


@pytest.mark.parametrize("name,traffic", [
    ("qwen4b-train-1chip", tiny.TRAIN), ("qwen4b-pipe4-int8", tiny.PIPE)])
def test_train_readers_read_the_step_by_scope(name, traffic):
    train_cell = tiny.cell(name, **traffic)
    hlo = scopes.train_step_hlo(train_cell, jax.devices()[:train_cell.chips])
    if train_cell.traffic["pipeline"]:
        assert "pipeline.hop" in scopes.scope_table(hlo).values()
    summary = _stand_in_trace(hlo, "jit_train_step", runs=2)
    obs = _obs(train_cell, summary, steps=2)
    got = {m: metrics.read(m, obs) for m in ("attention_ms", "mlp_ms",
                                             "head_loss_ms")}
    table = scopes.scope_table(hlo)
    for m, scope in (("attention_ms", "attention"), ("mlp_ms", "mlp"),
                     ("head_loss_ms", "head_loss")):
        want = sum(1 for s in table.values() if s == scope) * 1e-3
        assert got[m] == pytest.approx(want), m
    assert "hlo:train_step_hlo" in obs.run          # built once, shared
    # a program that runs nothing under the scopes reads as nothing
    bare = _obs(train_cell, summary, steps=2,
                **{"hlo:train_step_hlo": re.sub(
                    r'op_name="[^"]*"', 'op_name="jit(f)/add"', hlo)})
    assert metrics.read("attention_ms", bare) is None
    assert metrics.read("arena_ms", bare) is None
    assert metrics.read("attention_ms", _obs(train_cell, None,
                                             steps=2)) is None


def test_train_reader_of_a_nested_scope_outside_scopes():
    """``train_scope_ms`` of a name the program nests in ``mlp`` (the
    tiny step's MLP matmuls planted under ``mlp/mlp.dots``) reads those
    operations, and ``mlp_ms`` reads the whole ``mlp`` as before."""
    cell = tiny.cell("qwen4b-train-1chip", **tiny.TRAIN)
    hlo = scopes.train_step_hlo(cell, jax.devices()[:1])
    nested = re.sub(r'(op_name="[^"]*/mlp)/([^"/]*dot_general")',
                    r"\1/mlp.dots/\2", hlo)
    table, inner = (scopes.scope_table(nested),
                    scopes.scope_table(nested, scopes.with_scope("mlp.dots")))
    assert table == scopes.scope_table(hlo)
    dots = sum(1 for s in inner.values() if s == "mlp.dots")
    assert dots > 0
    obs = _obs(cell, _stand_in_trace(nested, "jit_train_step", runs=2),
               steps=2, **{"hlo:train_step_hlo": nested})
    assert scopes.train_scope_ms(obs, "mlp.dots") == pytest.approx(
        dots * 1e-3)
    mlp = sum(1 for s in table.values() if s == "mlp")
    assert metrics.read("mlp_ms", obs) == pytest.approx(mlp * 1e-3)
    assert mlp > dots


def test_arena_reader_reads_the_decode_step():
    cell = tiny.cell("qwen4b-serve-over-knee", **tiny.SERVE)
    hlo = scopes.decode_step_hlo(cell, jax.devices()[:1])
    summary = _stand_in_trace(hlo, "jit_step", runs=4)
    # a prefill program's operations in the window are not the step's
    summary.devices[0]["modules"].append(["jit__prefill_bucket(2)", 10 ** 9,
                                          5000])
    summary.devices[0]["ops"].append(["fusion.1 fusion", 10 ** 9, 5000])
    summary.host[0][2] = 2 * 10 ** 9
    obs = _obs(cell, summary)
    want = sum(1 for s in scopes.scope_table(hlo).values()
               if s == "arena") * 1e-3
    assert want > 0
    assert metrics.read("arena_ms", obs) == pytest.approx(want)
    assert metrics.read("attention_ms", obs) is None


def test_layers_tool_splits_the_decode_step_and_the_engines_idle():
    """``bench/layers.py``'s table on a stand-in trace of the tiny
    serving cell: scopes add up to the step, the engine's idle parts add
    up to ``engine.step``'s, and the lanes' occupancy is the counters'."""
    import layers
    cell = tiny.cell("qwen4b-serve-over-knee", **tiny.SERVE)
    hlo = scopes.decode_step_hlo(cell, jax.devices()[:1])
    summary = _stand_in_trace(hlo, "jit_step", runs=2)
    lo, hi = summary.window()
    summary.devices[0]["ops"] = [o for o in summary.devices[0]["ops"]
                                 if o[1] < hi // 2]        # idle after
    summary.host += [["step_once", hi // 2, hi // 2],
                     ["engine.step", hi // 2, hi // 2],
                     ["engine.decode", hi // 2, hi // 4],
                     ["engine.sync", hi // 2, hi // 8],
                     ["engine.schedule", 3 * hi // 4, hi // 8]]
    kept = {"summary": summary, "run": {"serve_tokens_per_s": 1.0},
            "lanes": [((10, 30), (4, 6))]}
    out = layers.layers(cell, kept, jax.devices()[:1])
    assert sum(out["by_scope_ms"].values()) == pytest.approx(
        out["ms_per_run"])
    assert out["by_scope_ms"]["arena"] > 0
    idle = out["idle_s"]
    assert idle["engine.step"] == pytest.approx(idle["step_once"])
    assert idle["engine.decode"] == pytest.approx(
        idle["engine.sync"] + out["idle_self_s"]["engine.decode"])
    assert out["idle_self_s"]["engine.step"] == pytest.approx(
        idle["engine.step"] - idle["engine.decode"]
        - idle["engine.schedule"])
    assert out["engine_steps"] == 1
    assert out["lane_occupancy"] == pytest.approx(
        100 * 24 / (tiny.SERVE["slots"] * 6))
