"""What a training run holds on the device: at every call of the train
step, one training state (the step's input) besides the feed and small
readings, in the first steps as in the window; and what ``rehearse.py``
prints of that cost."""
import gc
import time

import jax
import numpy as np
import pytest

from harness import train
from harness.clock import CompileClock

import tiny


def _state_shares(shares):
    """A ``wrap_step`` that records, at each call, the bytes of every live
    device array over the bytes of the state the step is given."""
    def wrap(step):
        def f(state, batch):
            held = sum(a.nbytes for a in jax.live_arrays())
            shares.append(held / sum(x.nbytes for x in jax.tree.leaves(state)))
            return step(state, batch)
        return f
    return wrap


def test_train_run_holds_one_state():
    cell = tiny.cell("qwen4b-train-1chip", **tiny.TRAIN)
    shares = []
    gc.collect()
    train.run(cell, jax.devices()[:1], 2 ** 33 + 29, 0.5,
              t_start=time.perf_counter(), clock=CompileClock(),
              wrap_step=_state_shares(shares))
    assert len(shares) > cell.traffic["ref_steps"]
    assert max(shares) <= 1.05, shares


def test_calibrate_first_steps_hold_one_state(monkeypatch):
    import calibrate
    cell = tiny.cell("qwen4b-train-1chip", **tiny.TRAIN)
    shares = []
    program = train.program

    def recorded(*args, **kw):
        prog = program(*args, **kw)
        return dict(prog, step=_state_shares(shares)(prog["step"]))

    monkeypatch.setattr(train, "program", recorded)
    gc.collect()
    calibrate.calibrate_train(cell, jax.devices()[:1],
                              [2 ** 33 + 31, 2 ** 33 + 37], 0)
    assert len(shares) == 2 * cell.traffic["ref_steps"]
    assert max(shares) <= 1.05, shares


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_rehearse_prints_the_cost_of_a_training_run(topo):
    import rehearse
    cell = tiny.cell("qwen4b-train-1chip", **tiny.TRAIN)
    got = rehearse.rehearse_train(cell, topo)
    prog = train.program(cell, jax.devices()[:1])
    leaves = jax.tree.leaves(prog["state_shapes"])
    assert got["state_bytes"] == sum(np.prod(x.shape) * x.dtype.itemsize
                                     for x in leaves)
    assert got["parameters"] == sum(np.prod(x.shape) for x in
                                    jax.tree.leaves(prog["shapes"]))
    assert got["bytes_per_parameter"] == (
        2 * got["state_bytes"] + got["train_step"]["temp_size_in_bytes"]
    ) / got["parameters"]
