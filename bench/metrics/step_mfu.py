"""The whole step's share of the chip's bf16 peak while the chip is
busy: required matmul FLOPs per step (the cell's program's
``step_flops``) x steps completed in the traced window, over the union
of the chip's operation intervals in it (mean over chips) x the peak.
Idle time is left to ``idle_share``; this bounds what a kernel's
roofline share can claim for the whole step."""
UNIT, LAYER, MOVES = "%", "train step", "mfu"


def read(r):
    return (100.0 * r.step_flops() * r.steps / r.busy_s
            / r.peak["bf16_flops_per_s"])
