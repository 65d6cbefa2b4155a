"""Per train step, the device ms of the operations under the program's
``head_loss`` scope (``models/lm.py`` ``LM.head_loss``: the final norm,
the head matmul and the chunked cross entropy, forward and backward),
averaged over the chips; each pipeline chip computes the head."""
from harness import scopes


def read(obs):
    return scopes.train_scope_ms(obs, "head_loss")
