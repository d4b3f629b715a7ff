"""Operations and bytes of one training step, from shapes alone.

Counts are per chip and per step, and count only the matrix products the
step needs: every layer's forward and weight gradient, and every input
gradient that some weight gradient depends on.  The first layer's
gradient with respect to the batch is not needed, so it is left out,
though the step's layer loop may compute it: that is up to a sixth less
than "6 x batch x parameters" at L = 2.  (Phantom's first layer still
needs the gradient of its ghosts, which its compressor's gradient takes.)

Phantom counts the paper's per-rank terms (``PhantomStrategy.flops``,
copied here, not imported): the local diagonal block (n/p)^2, the
compressor (n/p) k and the (p - 1) decompressors k (n/p).  The count is
the same whatever computes the products (XLA dots or a Pallas kernel).
"""
from __future__ import annotations


def gemms(cfg: dict, tp: int, batch: int) -> list:
    """The step's required products on one chip, as (m, k, n) triples:
    an [m, k] x [k, n] product each."""
    n, L, B = cfg["ffn_width"], cfg["num_layers"], batch
    # (forward product, whether the first layer needs its input gradient)
    if cfg["projection"] == "tensor":
        fwd = [((B, n, n // tp), False)]         # h [B, n] W[:, shard]
    else:
        p, k, m = tp, cfg["phantom"]["k"], n // tp
        fwd = [((B, m, m), False),               # x_j L_j
               ((B, m, k), False),               # g_j = x_j C_j
               ((B, (p - 1) * k, m), True)]      # sum_i g_i D_ij
    out = []
    for layer in range(L):
        for (b, i, o), first_needs in fwd:
            out.append((i, b, o))                # weight gradient
            out.append((b, i, o))                # forward
            if layer or first_needs:
                out.append((b, o, i))            # input gradient
    return out


def step_flops(cfg: dict, tp: int, batch: int) -> float:
    """Required matmul FLOPs of one step on one chip."""
    return float(sum(2 * m * k * n for m, k, n in gemms(cfg, tp, batch)))


def matmul_floor_s(cfg: dict, tp: int, batch: int, peak: dict) -> float:
    """Least time one chip can spend on the step's products.  Each
    product takes at least the longer of its FLOPs at the bf16 peak and
    its bytes at the HBM peak.  Bytes are the least a product can move:
    each operand read once and the result written once, at 2 bytes an
    element (the bf16 operands that a float32 dot at default precision
    feeds the MXU), so the floor never overstates."""
    return sum(max(2.0 * m * k * n / peak["bf16_flops_per_s"],
                   2.0 * (m * k + k * n + m * n) / peak["hbm_bytes_per_s"])
               for m, k, n in gemms(cfg, tp, batch))
