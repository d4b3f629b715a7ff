"""The seed of a run, as a key that every program's inputs and weights
are made from (``programs/<program>.py`` ``init_params`` and
``batch_pool``), the plain reference's included, so that both sides
start from the same weights and see the same batches."""
from __future__ import annotations

import jax


def root_key(seed: int):
    """A key for any whole seed >= 0: ``jax.random.key`` keeps only the
    low 32 bits, so the high ones are folded in.  Made outside ``jit``
    and passed in as an argument, so that every seed runs the same
    compiled programs, which the persistent cache then holds."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed % 2 ** 32), seed // 2 ** 32)
