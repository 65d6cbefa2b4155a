"""Seconds JAX spent compiling, or loading from its persistent cache,
the programs of set-up (its ``backend_compile_duration`` events)."""


def read(obs):
    return obs.run.get("compile_s")
