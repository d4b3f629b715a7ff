"""Device time per step in operations that are neither matmul nor
collective: AdamW, the activations, the loss, copies (mean over chips)."""
UNIT, LAYER, MOVES = "ms", "optimizer and elementwise", "samples_per_s"


def read(r):
    return r.class_ms("vector")
