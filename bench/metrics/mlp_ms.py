"""Per train step, the device ms of the operations under the program's
``mlp`` scope (``models/blocks.py`` ``_ffn``: the second norm and the
MLP, forward, recomputed forward and backward), averaged over the
chips."""
from harness import scopes


def read(obs):
    return scopes.train_scope_ms(obs, "mlp")
