"""Faults a training cell can have, planted in the reference that is put
in the program's place (``bench/calibrate.py`` reads them on the chip,
at the cell's own size, to set the upper end of each limit):

  half_batch   half of the batch left out, the mean taken over the rest
               (``harness.reference_steps(rows=batch // 2)``)
  no_exchange  the exchange between chips left out (multi-chip cells):
               the keywords that the cell's program's ``no_exchange``
               gives its reference
  unchanged    a step that returns its state unchanged: every change is
               0 and so is the first gradient in the optimizer's state,
               which reads 1 by the comparison's measure; it needs no run
"""
from __future__ import annotations


def unchanged(ref: dict) -> dict:
    """What a program whose step returns its state unchanged reports
    (its losses are left as the reference's: the norms alone read 1)."""
    return {"losses": list(ref["losses"]),
            "grad_norms": [0.0] * len(ref["grad_norms"]),
            "change_norms": [0.0] * len(ref["change_norms"])}
