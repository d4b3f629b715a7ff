"""Device time per step in the backward pass: the operations whose HLO
``op_name`` the program names ``transpose(jvp(model))``, recomputed
forward operations included (mean over chips); silent where the
program names no scope."""
from bench import scopes

UNIT, LAYER, MOVES = "ms", "backward", "samples_per_s"


def read(r):
    return scopes.read(r, "backward")
