"""Mean host time of ``engine.step_once()`` on iterations that admitted
no prefill chunk (the benchmark's span around the call)."""


def read(obs):
    return obs.run.get("decode_iter_ms")
