"""Mean host time of ``engine.step_once()`` on iterations that admitted a
prefill chunk (the benchmark's span around the call)."""


def read(obs):
    return obs.run.get("admit_iter_ms")
