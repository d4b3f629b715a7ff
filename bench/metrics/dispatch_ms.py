"""Host time of one step's dispatch: the harness's call into the jitted
step, until it returns (the ``bench.dispatch`` span), mean over the
traced window."""
UNIT, LAYER, MOVES = "ms", "entry and host loop", "samples_per_s"


def read(r):
    return r.host_ms("bench.dispatch")
