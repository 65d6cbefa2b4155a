#!/usr/bin/env python3
"""Record ``scopes.xplane.pb`` and ``scopes.hlo.txt`` on a TPU: a jitted
toy program whose two matmuls sit under the scopes ``attention`` and
``mlp`` (kept apart by an optimization barrier), run three times inside the serving engine's span names (a
host sleep in ``engine.schedule`` and ``engine.emit``, the dispatch in
``engine.decode``, the wait in ``engine.sync``), all inside the
benchmark's ``bench.window`` and ``step_once`` spans.

    python3 bench/tests/fixtures/record_scopes.py <out_dir>
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

#: host seconds the engine's schedule and emit spans spend with the
#: device idle
SCHEDULE_S, EMIT_S = 0.002, 0.001
STEPS = 3


@jax.jit
def toy(x, w):
    with jax.named_scope("attention"):
        y = jnp.tanh(x @ w)
    # keep the two scopes in operations of their own
    y = jax.lax.optimization_barrier(y)
    with jax.named_scope("mlp"):
        return jnp.maximum(y @ w, 0).sum()


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the fixture is a TPU trace: run it on the chip")
    span = jax.profiler.TraceAnnotation
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.full((1024, 1024), 0.001, jnp.bfloat16)
    toy(x, w).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with span("bench.window"):
        for _ in range(STEPS):
            with span("step_once"), span("engine.step"):
                with span("engine.schedule"):
                    time.sleep(SCHEDULE_S)
                with span("engine.decode"):
                    out = toy(x, w)
                    with span("engine.sync"):
                        out.block_until_ready()
                    with span("engine.emit"):
                        time.sleep(EMIT_S)
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, os.path.join(out_dir, "scopes.xplane.pb"))
    with open(os.path.join(out_dir, "scopes.hlo.txt"), "w") as f:
        f.write(toy.lower(x, w).compile().as_text())
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
