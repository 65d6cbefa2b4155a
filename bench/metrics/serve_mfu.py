"""Model FLOP/s of everything the engine served in the traced window
(each prompt's prefill and each served token's decode, with causal
attention over its context; the family's ``prefill_flops`` and
``decode_step_least``) as a share of the chip's bf16 peak."""
from harness import spec


def read(obs):
    run = obs.run
    if not run.get("window_wall_s"):
        return None
    cfg = obs.cell.config
    fam = spec.family(cfg)
    total = 0.0
    for positions in run["decode_positions"]:
        total += fam.decode_step_least(cfg, positions, 0, 0)[0]
    for plen in run["prefilled"]:
        total += fam.prefill_flops(cfg, plen)
    return 100.0 * total / (run["window_wall_s"] * obs.peaks["bf16_flops"])
