"""Device time per step in the optimizer update: the operations whose
HLO ``op_name`` the program names ``optimizer`` (mean over chips);
silent where the program names no scope."""
from bench import scopes

UNIT, LAYER, MOVES = "ms", "optimizer", "samples_per_s"


def read(r):
    return scopes.read(r, "optimizer")
