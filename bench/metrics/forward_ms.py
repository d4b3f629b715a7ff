"""Device time per step in the forward pass: the operations whose HLO
``op_name`` the program names ``jvp(model)`` (mean over chips); silent
where the program names no scope."""
from bench import scopes

UNIT, LAYER, MOVES = "ms", "forward", "samples_per_s"


def read(r):
    return scopes.read(r, "forward")
