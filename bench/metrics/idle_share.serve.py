"""Share of the traced window in which no operation ran on a chip,
averaged over the cell's chips."""
from harness import trace


def read(obs):
    if obs.trace is None or not obs.trace.devices:
        return None
    return 100.0 * trace.idle_share(obs.trace)
