"""Device time per step in matmul operations: XLA's convolution fusions
and the Pallas kernels' custom calls (mean over chips)."""
UNIT, LAYER, MOVES = "ms", "kernels", "samples_per_s"


def read(r):
    return r.class_ms("matmul")
