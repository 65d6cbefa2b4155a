"""The reduction from a profiler trace to the per-layer numbers.

``fixtures/matmul.xplane.pb`` is a trace recorded on a TPU v5e: a jitted
bf16 2048 x 2048 matmul-and-tanh called three times inside the
benchmark's ``bench.window`` and ``step_fn`` spans."""
import pytest

from harness import trace

import tiny


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_xplane(tiny.fixture("matmul.xplane.pb"))


def test_recorded_trace_reduces_to_one_chip_and_the_spans(summary):
    assert [d["id"] for d in summary.devices] == [0]
    names = [n for n, _, _ in summary.host]
    assert names.count("bench.window") == 1 and names.count("step_fn") == 3
    dev = summary.devices[0]
    assert [n for n, _, _ in dev["ops"]].count("fusion fusion") == 3
    assert len(dev["modules"]) == 3 and not dev["loops"]


def test_recorded_trace_busy_and_idle(summary):
    lo, hi = summary.window()
    assert trace.window_s(summary) == pytest.approx((hi - lo) / 1e9)
    fusions = [(t, d) for n, t, d in summary.devices[0]["ops"]
               if n == "fusion fusion"]
    inside = sum(min(t + d, hi) - max(t, lo) for t, d in fusions
                 if t + d > lo and t < hi)
    assert trace.busy_s(summary) >= inside / 1e9
    assert 0.0 < trace.idle_share(summary) < 1.0
    bd = trace.breakdown(summary)
    assert bd["device_ops"][0][0] == "fusion fusion"
    assert sum(s for _, s in bd["idle_gaps"]) == pytest.approx(
        trace.window_s(summary) - trace.busy_s(summary), rel=1e-6)


def _summary(ops, loops=(), host=(), modules=()):
    return trace.Summary(
        devices=[{"id": 0, "ops": [list(o) for o in ops],
                  "loops": [list(o) for o in loops],
                  "modules": [list(m) for m in modules]}],
        host=[["bench.window", 0, 100]] + [list(h) for h in host])


def test_exposed_time_is_what_no_other_op_covers():
    s = _summary([("cp collective-permute-done", 10, 10),
                  ("fusion.1 fusion", 15, 10),
                  ("cp collective-permute-done", 50, 5)])
    hop = lambda n: "collective-permute" in n  # noqa: E731
    assert trace.exposed_seconds(s, hop) == pytest.approx((5 + 5) / 1e9)
    assert trace.op_seconds(s, hop) == pytest.approx(15 / 1e9)
    assert trace.op_count(s, hop) == 2


def test_loops_count_as_busy_but_not_as_operations():
    s = _summary([("fusion.1 fusion", 10, 10)], loops=[("while.1 while",
                                                         5, 30)])
    assert trace.busy_s(s) == pytest.approx(30 / 1e9)
    assert trace.breakdown(s)["device_ops"] == [["fusion.1 fusion", 1e-8]]


def test_idle_gaps_are_labelled_by_host_spans():
    s = _summary([("fusion.1 fusion", 0, 40), ("fusion.2 fusion", 60, 40)],
                 host=[("step_once", 45, 10)])
    gaps = dict(trace.breakdown(s)["idle_gaps"])
    assert gaps == pytest.approx({"step_once": 10e-9, "host": 10e-9})


def test_short_name_keeps_kernel_types():
    text = ('%_lambda_.1 = (s8[4096,10,256]{2,1,0:T(8,128)(4,1)S(1)}, '
            'f32[4096,10,1]{2,1,0:T(8,128)S(1)}) custom-call(%bitcast.7), '
            'custom_call_target="tpu_custom_call", operand_layout_'
            'constraints={bf16[4096,2560]{1,0}}, backend_config={}')
    assert trace.short_name(text) == (
        "_lambda_.1 custom-call:tpu_custom_call (s8[4096,10,256], "
        "f32[4096,10,1]) <- (bf16[4096,2560])")
    assert trace.short_name(
        "%fusion.588 = (f32[20,512]{1,0:T(8,128)S(1)}, f32[2]{0}) "
        "fusion(f32[2]{0} %x), kind=kOutput") == "fusion.588 fusion"
