"""Synthetic datasets.

1. The paper's Gaussian-teacher dataset (§VI "Data and Hardware"):
   a fixed standard-Gaussian W in R^{n x n}; samples (x, y) with
   y = sigma(W sigma(x)), sigma = ReLU.  Used to train TP and PP FFNs to a
   fixed loss for the energy comparisons (Table I / Fig. 7).

2. Deterministic token streams for the LM architectures: a fixed-seed
   zipf-ish categorical over the vocab with a simple induction pattern so
   a ~100M model's loss visibly decreases within a few hundred steps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def gaussian_teacher(n: int, seed: int = 0, scale: float | None = None):
    """The paper's fixed teacher matrix W ~ N(0,1)^{n x n} (scaled for
    numerical sanity; the paper uses standard normal)."""
    rng = np.random.default_rng(seed)
    scale = scale if scale is not None else n ** -0.5
    return jnp.asarray(rng.standard_normal((n, n)) * scale, jnp.float32)


def teacher_batch(W, batch: int, seed: int):
    """(x, y) with y = relu(W relu(x)) — paper §VI."""
    key = jax.random.fold_in(jax.random.key(17), seed)
    x = jax.random.normal(key, (batch, W.shape[0]), jnp.float32)
    y = jax.nn.relu(jax.nn.relu(x) @ W)
    return x, y


class TeacherDataset:
    """Streaming batches of the paper's dataset, deterministic per step."""

    def __init__(self, n: int, batch: int, seed: int = 0):
        self.W = gaussian_teacher(n, seed)
        self.batch = batch
        # W is an argument, not a closure: a closed-over array is baked
        # into the program as a constant (1 GiB at n=16384)
        self._make = jax.jit(teacher_batch, static_argnums=1)

    def __call__(self, step: int):
        return self._make(self.W, self.batch, jnp.int32(step))


def lm_token_batch(vocab: int, batch: int, seq: int, seed: int,
                   pattern_period: int = 17):
    """Deterministic pseudo-text: categorical tokens + a copy pattern every
    `pattern_period` positions, so next-token loss is learnable."""
    key = jax.random.fold_in(jax.random.key(29), seed)
    base = jax.random.randint(key, (batch, seq), 0, vocab)
    pos = jnp.arange(seq)
    shifted = jnp.roll(base, pattern_period, axis=1)
    tokens = jnp.where((pos % pattern_period == 0)[None, :], shifted, base)
    return tokens.astype(jnp.int32)


class LMDataset:
    def __init__(self, vocab: int, batch: int, seq: int, seed: int = 0):
        self.vocab, self.batch, self.seq = vocab, batch, seq
        self.seed = seed
        self._make = jax.jit(
            lambda s: lm_token_batch(vocab, batch, seq, s))

    def __call__(self, step: int):
        toks = self._make(jnp.int32(step) + self.seed * 100003)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
