"""Per decode step, the device ms of the operations under the program's
``arena`` scope in the engine's ``jit_step`` (``serving/engine.py``: each
lane's slot expanded and squeezed, and the ``where_slots`` merge of the
whole arena)."""
from harness import scopes


def read(obs):
    return scopes.decode_scope_ms(obs, "arena")
