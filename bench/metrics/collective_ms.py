"""Device time per step in which a collective (all-gather, reduce-scatter,
all-reduce) runs or is in flight, mean over chips; silent where the step
has none."""
UNIT, LAYER, MOVES = "ms", "projections", "samples_per_s"


def read(r):
    return r.class_ms("collective")
