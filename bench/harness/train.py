"""Training cells: the program's compiled train step, timed over a window
and checked against the plain reference.

Set-up builds one object, the jitted ``make_lm_train_step`` with its
state, drives it through its first ``ref_steps`` steps on rows that all
differ (compiling on the first), and hands that same object to the
window.  From those first steps it keeps each loss, the per-leaf norms
of the first gradient as AdamW holds it (``m / (1 - b1)`` after one
step) and of the parameters' change.  After the window, with the
program's state freed, the reference takes the same steps on the same
rows and ``compare`` sets the two side by side.
"""
from __future__ import annotations

import math
import statistics
import time

import jax
import jax.numpy as jnp

from harness import spec, weights
from harness.clock import CompileClock

FEED = 16      # distinct batches the window cycles through


def _leaf_norms_fn(scale: float):
    def norms(tree):
        return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32)))) * scale, tree)
    return jax.jit(norms)


def _named(tree) -> dict:
    return {name: float(v) for name, v in
            zip(weights.leaf_names(tree), jax.tree.leaves(tree))}


def program(cell, devices, mesh=None):
    """The program's jitted train step for this cell, with the shardings
    of its state and batch.  ``mesh`` replaces the pipeline's host mesh
    (a described chip's devices, for ``rehearse.py``)."""
    from repro.models.lm import LM
    from repro.parallel.steps import make_lm_train_step
    from repro.training.optim import adamw

    tr, cfg_json = cell.traffic, cell.config
    fam = spec.family(cfg_json)
    model = LM(fam.lm_config(cfg_json, name=cell.config_name))
    opt = adamw(tr["lr"], b1=tr["b1"], b2=tr["b2"], eps=tr["eps"])
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    want = fam.param_shapes(cfg_json)
    got = {n: tuple(s.shape) for n, s in
           zip(weights.leaf_names(shapes), jax.tree.leaves(shapes))}
    if got != want:
        raise RuntimeError(f"the program's parameters {got} are not the "
                           f"reference's {want}")
    state_shapes = jax.eval_shape(lambda p: {
        "params": p, "opt_state": opt.init(p),
        "step": jnp.zeros((), jnp.int32)}, shapes)
    pipe = tr.get("pipeline")
    if pipe:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_host_mesh
        from repro.launch.train import place_pipeline_state
        from repro.parallel.pipeline import PipelineSpec
        from repro.parallel.sharding import ShardingPolicy
        pspec = PipelineSpec(num_stages=pipe["stages"],
                             microbatches=tr["microbatches"],
                             virtual_stages=pipe["virtual_stages"],
                             wire_dtype=pipe["wire_dtype"])
        mesh = mesh or make_host_mesh(pod=pipe["stages"])
        shardings = ShardingPolicy(mesh, pod_is_pipeline=True
                                   ).train_state_shardings(state_shapes)
        step = make_lm_train_step(model, opt, pipeline=pspec, mesh=mesh)
        batch_sharding = NamedSharding(mesh, P())
        place = lambda s: place_pipeline_state(s, mesh)  # noqa: E731
    else:
        one = jax.sharding.SingleDeviceSharding(devices[0])
        shardings = jax.tree.map(lambda _: one, state_shapes)
        step = make_lm_train_step(model, opt,
                                  microbatches=tr["microbatches"])
        batch_sharding = one
        place = lambda s: s  # noqa: E731

    def init(key):
        params = weights.make_params(key, shapes)
        return {"params": params, "opt_state": opt.init(params),
                "step": jnp.zeros((), jnp.int32)}

    return {"step": jax.jit(step), "shapes": shapes,
            "state_shapes": state_shapes, "shardings": shardings,
            "batch_sharding": batch_sharding, "place": place, "init": init}


def materialize(prog, cell, seed: int):
    """The feed drawn from the seed: ``FEED`` batches of distinct rows, on
    the device and (as numpy) for the reference.  The state is drawn in
    ``first_steps``, so that nothing but the step that consumes it holds
    it."""
    tr = cell.traffic
    rows = [weights.token_rows(seed, tr["batch"], tr["seq"] + 1,
                               cell.config["vocab_size"], stream=i)
            for i in range(FEED)]
    feed = [jax.device_put({"tokens": r[:, :-1], "labels": r[:, 1:]},
                           prog["batch_sharding"]) for r in rows]
    return feed, rows


def first_steps(step_fn, prog, cell, feed, seed: int):
    """The first ``ref_steps`` steps (the first compiles) from the state
    drawn from the seed and placed as the program places it; returns the
    readings the reference is compared with, and the state.  The state is
    drawn here and bound to one name, which each step rebinds: at every
    call the device holds the step's input state and, while it runs, its
    output, and never a third (the step is not donated)."""
    tr = cell.traffic
    grad_norms = _leaf_norms_fn(1.0 / (1.0 - tr["b1"]))
    change_norms = jax.jit(lambda p, key: jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))),
        p, weights.make_params(key, prog["shapes"])))
    state = prog["place"](jax.jit(prog["init"],
                                  out_shardings=prog["shardings"])(
        weights.seed_key(seed)))
    losses = []
    for i in range(tr["ref_steps"]):
        state, mets = step_fn(state, feed[i])
        losses.append(float(mets["loss"]))
        if i == 0:
            g1 = _named(grad_norms(state["opt_state"]["m"]))
    dp = _named(change_norms(state["params"], weights.seed_key(seed)))
    return {"losses": losses, "grad": g1, "change": dp}, state


def run(cell, devices, seed: int, seconds: float, *, t_start: float,
        clock: CompileClock, trace_dir: str | None = None,
        wrap_step=None) -> dict:
    """One run of a training cell.  ``wrap_step`` (tests only) plants a
    fault in the step the window drives."""
    tr = cell.traffic
    n_ref = tr["ref_steps"]
    prog = program(cell, devices)
    step_fn = prog["step"] if wrap_step is None else wrap_step(prog["step"])
    feed, rows = materialize(prog, cell, seed)
    readings, state = first_steps(step_fn, prog, cell, feed, seed)

    # the window: whole steps, one in flight, until `seconds` has passed.
    # The queued step's output is allocated while the running step still
    # holds its input: three states where they fit (`memory_peak_bytes`
    # reads them); where they do not, the queued step waits.
    tokens = tr["batch"] * tr["seq"]
    compiles0, compile_s = clock.programs, clock.seconds
    result: dict = {}

    def window():
        nonlocal state
        t0 = time.perf_counter()
        result["setup_s"] = t0 - t_start
        n, pending = 0, None
        while True:
            batch = feed[(n_ref + n) % FEED]
            with jax.profiler.TraceAnnotation("step_fn"):
                state, mets = step_fn(state, batch)
            n += 1
            if pending is not None:
                float(pending["loss"])
            pending = mets
            if time.perf_counter() - t0 >= seconds:
                break
        float(pending["loss"])
        return n, time.perf_counter() - t0

    if trace_dir:
        from harness.trace import capture
        with capture(trace_dir, result):
            steps, elapsed = window()
    else:
        steps, elapsed = window()
    result.update(
        steps=steps, window_s=elapsed, compile_s=compile_s,
        compiles_in_window=clock.programs - compiles0,
        train_tokens_per_s=steps * tokens / elapsed,
        attempted=steps, failed=0,
        memory_peak_bytes=peak_bytes(devices))

    # free the program's state, then the reference's steps on the same rows
    state = feed = step_fn = prog = None
    result["checks"] = compare(
        readings, reference_readings(reference(cell, seed, devices), rows,
                                     n_ref), cell.limits)
    return result


def reference(cell, seed: int, devices, **options):
    """The family's plain reference for this cell, with the traffic's
    AdamW and micro-batches; ``options`` as ``Reference`` takes them
    (``precision``, ``rows``, ``drop_exchange``)."""
    tr = cell.traffic
    return spec.family(cell.config).Reference(
        cell.config, seed, devices,
        adam=(tr["lr"], tr["b1"], tr["b2"], tr["eps"]),
        microbatches=tr["microbatches"], **options)


def reference_readings(ref, rows, n_steps: int) -> dict:
    losses = []
    for i in range(n_steps):
        loss, grads = ref.train_step(rows[i])
        losses.append(loss)
        if i == 0:
            g1 = ref.leaf_norms(grads)
        del grads
    return {"losses": losses, "grad": g1, "change": ref.change_norms()}


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def compare(got: dict, want: dict, limits: dict) -> list:
    """[(name, value, limit)] for one run: the widest loss gap over the
    steps, and by the worst leaf the gap between the norms of the first
    gradient and of the parameters' change, each against the larger of
    the reference's norm of that leaf and of the median leaf.  Leaves
    whose reference gradient is under a thousandth of the median leaf's
    are left out of the change: they move under AdamW by round-off."""
    loss_gap = _worst(abs(a - b) for a, b in zip(got["losses"],
                                                 want["losses"]))
    g_med = statistics.median(want["grad"].values())
    grad_gap = _worst(abs(got["grad"][k] - v) / max(v, g_med)
                      for k, v in want["grad"].items())
    moving = [k for k in want["grad"] if k not in left_out(want)]
    d_med = statistics.median(want["change"][k] for k in moving)
    change_gap = _worst(abs(got["change"][k] - want["change"][k])
                        / max(want["change"][k], d_med) for k in moving)
    vals = {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "change_norm_gap": change_gap}
    return [(k, vals[k], limits[k]) for k in ("loss_gap", "grad_norm_gap",
                                              "change_norm_gap")]


def left_out(want: dict) -> list:
    """Leaves whose reference gradient is under a thousandth of the median
    leaf's: their change is round-off under AdamW."""
    g_med = statistics.median(want["grad"].values())
    return sorted(k for k, v in want["grad"].items() if v < 1e-3 * g_med)


def _worst(values) -> float:
    """The largest value; a NaN or infinity reads as infinity."""
    return max(v if math.isfinite(v) else math.inf for v in values)
