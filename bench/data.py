"""Inputs and weights of a run, made on the device from ``--seed``.

Both the program under test and the plain reference are fed from here,
so they start from the same weights and see the same batches; neither
takes anything the other made.  The layouts follow the parameter tree
the program's ``init_ffn`` declares (``{"layers": {leaf: [L, ...]}}``);
the harness checks that they still agree before a run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def root_key(seed: int):
    """A key for any whole seed >= 0: ``jax.random.key`` keeps only the
    low 32 bits, so the high ones are folded in.  Made outside ``jit``
    and passed in as an argument, so that every seed runs the same
    compiled programs, which the persistent cache then holds."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed % 2 ** 32), seed // 2 ** 32)


def param_layout(cfg: dict, tp: int) -> dict:
    """leaf name -> (per-layer shape, init std)."""
    n = cfg["ffn_width"]
    if cfg["projection"] == "tensor":
        return {"w": ((n, n), n ** -0.5), "b": ((n,), 0.0)}
    k, m = cfg["phantom"]["k"], n // tp
    return {"L": ((tp, m, m), m ** -0.5),
            "C": ((n, k), m ** -0.5),
            "D": ((tp, k, n), ((tp - 1) * k) ** -0.5),
            "b": ((n,), 0.0)}


def leaf_names(cfg: dict, tp: int) -> list:
    """(leaf, layer) pairs in the order every per-leaf vector uses."""
    return [(name, l) for name in sorted(param_layout(cfg, tp))
            for l in range(cfg["num_layers"])]


def init_params(cfg: dict, tp: int, key, dtype=jnp.float32):
    """Initial parameters ``{"layers": {leaf: [L, ...]}}`` from the
    seed's ``root_key``; call under ``jax.jit`` (with the step's
    shardings as ``out_shardings``)."""
    key = jax.random.fold_in(key, 0)
    layers = {}
    for i, (name, (shape, std)) in enumerate(
            sorted(param_layout(cfg, tp).items())):
        shape = (cfg["num_layers"],) + shape
        if std == 0.0:
            layers[name] = jnp.zeros(shape, dtype)
        else:
            layers[name] = (jax.random.normal(jax.random.fold_in(key, i),
                                              shape, jnp.float32)
                            * std).astype(dtype)
    return {"layers": layers}


def teacher_pool(cfg: dict, traffic: dict, key):
    """The paper's Gaussian teacher: ``pool_batches`` batches
    ``(x, y)``, ``x ~ N(0, 1)``, ``y = relu(relu(x) W)`` with one
    ``W ~ N(0, 1/n)`` per seed (``key`` is its ``root_key``).  Returns
    ``[x0, y0, x1, y1, ...]``; call under ``jax.jit`` on one device, so
    that every run of a seed, the reference's included, gets the same
    bits."""
    n, b = cfg["ffn_width"], traffic["global_batch"]
    kw, kx = jax.random.split(jax.random.fold_in(key, 1))
    w = jax.random.normal(kw, (n, n), jnp.float32) * n ** -0.5
    out = []
    for i in range(traffic["pool_batches"]):
        x = jax.random.normal(jax.random.fold_in(kx, i), (b, n), jnp.float32)
        out += [x, jax.nn.relu(jax.nn.relu(x) @ w)]
    return out
