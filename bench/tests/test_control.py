"""The control: the reference itself, put in the program's place and
computed in float8, the precision below the configuration's bfloat16,
must come out not correct.  On the chip it is read at the cells' own
sizes by ``bench/calibrate.py``; here at a size a test run can hold."""
import jax

import calibrate
import tiny


def _fails(readings, limits):
    return any(v > limits[k] for k, v in readings.items())


def test_training_control_is_not_correct():
    cell = tiny.cell("qwen4b-train-1chip", **tiny.TRAIN)
    rec = calibrate.calibrate_train(cell, jax.devices()[:1],
                                    [2 ** 35 + 1], faults=1)[0]
    assert _fails(rec["control"], cell.limits), rec
    assert _fails(rec["half_batch"], cell.limits), rec


def test_pipeline_control_is_not_correct():
    cell = tiny.cell("qwen4b-pipe4-int8", **tiny.PIPE)
    rec = calibrate.calibrate_train(cell, jax.devices()[:4],
                                    [2 ** 35 + 2], faults=1)[0]
    for variant in ("control", "half_batch", "no_exchange"):
        assert _fails(rec[variant], cell.limits), (variant, rec)


def test_serving_control_reads_far_above_the_program():
    """At this size the logits are a tenth of the cell's, so the control is
    held against the program's own reading rather than the cell's limit."""
    cell = tiny.cell("qwen4b-serve-over-knee", **tiny.SERVE)
    rec = calibrate.calibrate_serve(cell, jax.devices()[:1], [2 ** 35 + 3],
                                    faults=1, seconds=2.0)[0]
    prog = rec["program"]["served_logit_gap"]
    assert rec["control"]["served_logit_gap"] > 3 * prog, rec
    assert rec["altered_token"]["served_logit_gap"] > 3 * prog, rec
