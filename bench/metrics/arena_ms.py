"""Per decode step, the device ms of the operations under the program's
``arena`` scope in the engine's ``jit_step`` (``models/blocks.py``
``_write_token``: one token's keys and values per lane per layer,
written by a dynamic-update-slice into the arena the engine donates to
the step, and nothing else of the arena)."""
from harness import scopes


def read(obs):
    return scopes.decode_scope_ms(obs, "arena")
