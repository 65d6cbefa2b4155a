"""Model FLOP/s of the whole train step as a share of the chips' bf16
peak: model FLOPs per token (the family's ``train_flops_per_token``: no
recomputation, no masked pipeline tick, one copy of the head) times the
traced window's tokens per second, over chips times peak."""
from harness import spec


def read(obs):
    rate = obs.run.get("train_tokens_per_s")
    if not rate:
        return None
    cfg = obs.cell.config
    per_token = spec.family(cfg).train_flops_per_token(
        cfg, obs.cell.traffic["seq"])
    return 100.0 * per_token * rate / (obs.chips * obs.peaks["bf16_flops"])
