"""The part of the collective time per step during which no other
operation runs on that chip (mean over chips); silent where the step
has no collective."""
UNIT, LAYER, MOVES = "ms", "projections", "samples_per_s"


def read(r):
    if r.class_ms("collective") is None:
        return None
    return r.per_step_ms(r.exposed_collective_s)
