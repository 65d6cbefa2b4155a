"""Model FLOP/s of the whole train step as a share of the chips' bf16
peak: model FLOPs per token (``flops.train_flops_per_token``: no
recomputation, no masked pipeline tick, one copy of the head) times the
traced window's tokens per second, over chips times peak."""
from harness import flops


def read(obs):
    rate = obs.run.get("train_tokens_per_s")
    if not rate:
        return None
    per_token = flops.train_flops_per_token(obs.cell.config,
                                            obs.cell.traffic["seq"])
    return 100.0 * per_token * rate / (obs.chips * obs.peaks["bf16_flops"])
