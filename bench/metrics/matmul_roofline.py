"""Share of its roofline that the step's matmul time reaches: the least
time one chip can spend on the step's required products (the cell's
program's ``matmul_floor_s``: each product at the longer of its FLOPs at
the bf16 peak and its least bytes at the HBM peak) over the measured
matmul time."""
UNIT, LAYER, MOVES = "%", "kernels", "mfu"


def read(r):
    ms = r.class_ms("matmul")
    if ms is None:
        return None
    return 100.0 * r.matmul_floor_s() * 1e3 / ms
