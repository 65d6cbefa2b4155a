#!/usr/bin/env python3
"""Compile each cell's programs for a TPU v5e that is described, not
attached, and print what the chip's compiler says of their memory.

    JAX_PLATFORMS=cpu python bench/rehearse.py [--workload <name> ...]

Training cells compile their train step at the cell's sizes (the
pipeline cell over a described ``v5e:2x2``); the serving cell compiles
its decode step and its largest prefill chunk.  Nothing runs: a compile
that passes says the program fits and lowers, not how fast it is.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402


def _steer_to_tpu():
    """The default backend here is the CPU: send the kernels and the
    codec down their TPU paths, as on the chip."""
    from repro.kernels import ops
    from repro.parallel import wire
    ops._interpret = lambda: False
    impl = wire._impl
    wire._impl = lambda i: "fused" if i == "auto" else impl(i)


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys}


def _with(shapes, shardings):
    return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), shapes, shardings)


def _on_chip(x) -> int:
    """Elements of ``x``'s shard on one chip (the shards are alike)."""
    return int(np.prod(x.sharding.shard_shape(x.shape)))


def rehearse_train(cell, topo) -> dict:
    """The step's memory as the chip's compiler counts it, and what a
    training run costs at its peak: two states on the fullest chip (the
    step's input and its output; the step is not donated) and the step's
    temp, as ``bytes_per_parameter`` over the parameters held there."""
    from harness import train
    from repro.parallel.compat import make_mesh
    pipe = cell.traffic.get("pipeline")
    mesh = None
    if pipe:
        devs = np.array(topo.devices[:pipe["stages"]])
        mesh = make_mesh((pipe["stages"], 1, 1), ("pod", "data", "model"),
                         devices=devs)
    prog = train.program(cell, topo.devices[:1], mesh=mesh)
    tr = cell.traffic
    tok = jax.ShapeDtypeStruct((tr["batch"], tr["seq"]), np.int32,
                               sharding=prog["batch_sharding"])
    state = _with(prog["state_shapes"], prog["shardings"])
    compiled = prog["step"].lower(state, {"tokens": tok,
                                          "labels": tok}).compile()
    text = compiled.as_text()
    mem = _mem(compiled)
    state_bytes = sum(_on_chip(x) * x.dtype.itemsize
                      for x in jax.tree.leaves(state))
    parameters = sum(_on_chip(x) for x in jax.tree.leaves(state["params"]))
    return {"train_step": mem, "state_bytes": state_bytes,
            "parameters": parameters,
            "bytes_per_parameter": (2 * state_bytes
                                    + mem["temp_size_in_bytes"]) / parameters,
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "collective_permutes": text.count("collective-permute-start")
            or text.count("collective-permute(")}


def rehearse_serve(cell, topo) -> dict:
    from harness import serve
    from repro.models.lm import LM
    from repro.serving import kv
    from harness import spec
    tr = cell.traffic
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    model = LM(spec.family(cell.config).lm_config(cell.config))
    params = _with(jax.eval_shape(model.init, jax.random.key(0)),
                   jax.tree.map(lambda _: one, jax.eval_shape(
                       model.init, jax.random.key(0))))
    slots, cache_len = tr["slots"], tr["cache_len"]
    cache = jax.eval_shape(lambda: model.init_cache(slots, cache_len,
                                                    np.float32))
    cache = _with(cache, jax.tree.map(lambda _: one, cache))
    lane = jax.ShapeDtypeStruct((slots,), np.int32, sharding=one)
    act = jax.ShapeDtypeStruct((slots,), bool, sharding=one)
    from repro.serving.engine import ServingEngine
    step = ServingEngine._build_step(type("E", (), {
        "model": model, "axes": kv.slot_axes(model, cache_len, np.float32),
        "temperature": 0.0, "seed": 0})())
    dec = jax.jit(step).lower(params, cache, lane, act, lane, lane,
                              lane).compile()
    rows, plen = max(serve.warm_shapes(tr, slots), key=lambda s: s[0] * s[1])
    tokens = jax.ShapeDtypeStruct((rows, plen), np.int32, sharding=one)
    pre = jax.jit(lambda p, t: model.prefill_with_cache(
        p, {"tokens": t}, cache_len=cache_len, cache_dtype=np.float32)
    ).lower(params, tokens).compile()
    return {"decode_step": _mem(dec), f"prefill_{rows}x{plen}": _mem(pre)}


def main(argv=None) -> int:
    from jax.experimental import topologies
    from harness import spec
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    bench = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    names = args.workload or [w["name"] for w in bench["workloads"]]
    jax.config.update("jax_enable_compilation_cache", False)
    _steer_to_tpu()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        cell = spec.load_cell(name)
        fn = rehearse_serve if cell.traffic["kind"] == "serve" \
            else rehearse_train
        print(json.dumps({"workload": name, **fn(cell, topo)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
