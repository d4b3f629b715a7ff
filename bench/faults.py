"""Faults a training cell can have, planted in the reference that is put
in the program's place (``bench/calibrate.py`` reads them on the chip,
at the cell's own size, to set the upper end of each limit):

  half_batch   half of the batch left out, the mean taken over the rest
               (``harness.reference_steps(rows=batch // 2)``)
  no_exchange  the exchange between chips left out: rank j sees only its
               own feature block, so tensor's all-gather leaves W
               block-diagonal and phantom loses its ghost terms
  unchanged    a step that returns its state unchanged: every change is
               0 and so is the first gradient in the optimizer's state,
               which reads 1 by the comparison's measure; it needs no run
"""
from __future__ import annotations

import jax.numpy as jnp


def no_exchange(projection: str, tp: int):
    """A reference layer with the exchange between the tp ranks left out."""
    if projection == "tensor":
        def layer(h, p):
            w, m = p["w"], p["w"].shape[0] // tp
            diag = jnp.stack([w[j * m:(j + 1) * m, j * m:(j + 1) * m]
                              for j in range(tp)])
            B = h.shape[0]
            z = jnp.einsum("bjm,jmo->bjo", h.reshape(B, tp, -1), diag)
            return z.reshape(B, -1) + p["b"]
        return layer

    def layer(h, p):
        B = h.shape[0]
        x = h.reshape(B, tp, -1)
        return (jnp.einsum("bjm,jmo->bjo", x, p["L"]).reshape(B, -1)
                + p["b"])
    return layer


def unchanged(ref: dict) -> dict:
    """What a program whose step returns its state unchanged reports
    (its losses are left as the reference's: the norms alone read 1)."""
    return {"losses": list(ref["losses"]),
            "grad_norms": [0.0] * len(ref["grad_norms"]),
            "change_norms": [0.0] * len(ref["change_norms"])}
