"""Per train step, the device time of the pipeline hop's collective-
permutes during which no other operation runs on that chip, averaged
over the chips."""
from harness import trace


def is_hop(name: str) -> bool:
    return "collective-permute" in name


def read(obs):
    if obs.trace is None or not obs.cell.traffic.get("pipeline"):
        return None
    if not trace.op_count(obs.trace, is_hop):
        return None
    return 1e3 * trace.exposed_seconds(obs.trace, is_hop) / obs.run["steps"]
