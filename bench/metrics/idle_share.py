"""Share of the traced window in which no operation runs on the chip:
1 - (union of the chip's operation intervals) / window, mean over
chips."""
UNIT, LAYER, MOVES = "%", "compiler and device", "samples_per_s"


def read(r):
    return 100.0 * (1.0 - r.busy_s / r.window_s)
