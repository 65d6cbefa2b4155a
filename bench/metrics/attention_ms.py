"""Per train step, the device ms of the operations under the program's
``attention`` scope (``models/blocks.py``: the first norm, the QKV
projection, RoPE, the attention itself and the output projection, in the
forward, the recomputed forward and the backward), averaged over the
chips.  The scope is read from the train step's HLO metadata."""
from harness import scopes


def read(obs):
    return scopes.train_scope_ms(obs, "attention")
