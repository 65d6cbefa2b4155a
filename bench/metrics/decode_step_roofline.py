"""The decode program's share of its roofline: for every decode step of
the traced window, the larger of its FLOPs over peak and its least bytes
over HBM bandwidth (the family's ``decode_step_least``: the weights as
stored, and each served lane's keys and values up to its position),
summed, over the decode program's device time."""
import re

from harness import spec, trace

#: the engine's jitted decode-and-sample program (``ServingEngine._step``)
DECODE = re.compile(r"^jit_step\b")


def read(obs):
    if obs.trace is None or not obs.run.get("decode_positions"):
        return None
    cfg, p = obs.cell.config, obs.peaks
    fam = spec.family(cfg)
    least = 0.0
    for positions in obs.run["decode_positions"]:
        f, b = fam.decode_step_least(cfg, positions,
                                     obs.run["weight_itemsize"],
                                     obs.run["kv_itemsize"])
        least += max(f / p["bf16_flops"], b / p["hbm_bytes_per_s"])
    busy = trace.module_seconds(obs.trace, lambda n: bool(DECODE.match(n)))
    if busy <= 0:
        return None
    return 100.0 * least / busy
