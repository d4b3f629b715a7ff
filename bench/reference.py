"""Plain reference of the paper FFN's training step, on global arrays.

Written from the layer equations, not from the program: it imports
nothing of ``repro``.  It runs in float32 at ``highest`` matmul
precision, as the configurations state their parameters in float32;
the same code in bfloat16 is the control that the comparison has to
fail (``bench/calibrate.py``).

  tensor  (dense, or tensor-parallel: the same function)
          h <- relu(h W + b)
  phantom with the features split in p blocks, x_j the j-th block,
          z_j = x_j L_j + sum_{i != j} (x_i C_i) D_ij + b_j,
          h <- relu(z)
  loss    mean((h_L - y)^2) over every element
  AdamW   m <- b1 m + (1-b1) g,  v <- b2 v + (1-b2) g^2,
          p <- p - lr (m/(1-b1^t) / (sqrt(v/(1-b2^t)) + eps) + wd p)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import data

FIRST_STEPS = 3        # steps the comparison follows


def tensor_layer(h, w, b):
    return h @ w + b


def phantom_layer(h, L, C, D, b):
    B, n = h.shape
    p, m, _ = L.shape
    k = C.shape[1]
    x = h.reshape(B, p, m)                                  # x_j
    g = jnp.einsum("bim,imk->bik", x, C.reshape(p, m, k))   # g_i = x_i C_i
    local = jnp.einsum("bjm,jmo->bjo", x, L)                # x_j L_j
    d = D.reshape(p, k, p, m)                               # D_ij
    off = (1 - jnp.eye(p)).astype(h.dtype)                  # i != j
    cross = jnp.einsum("bik,ikjo,ij->bjo", g, d, off)
    return (local + cross).reshape(B, n) + b


LAYERS = {"tensor": lambda h, p: tensor_layer(h, p["w"], p["b"]),
          "phantom": lambda h, p: phantom_layer(h, p["L"], p["C"], p["D"],
                                                p["b"])}


def loss_fn(cfg, layers, x, y, layer=None):
    layer = layer or LAYERS[cfg["projection"]]
    h = x
    for l in range(cfg["num_layers"]):
        h = jax.nn.relu(layer(h, {k: a[l] for k, a in layers.items()}))
    return jnp.mean(jnp.square(h - y))


def leaf_norms(cfg, tp, layers):
    """Norm of each (leaf, layer) in ``data.leaf_names`` order, in f32."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        layers[name][l].astype(jnp.float32))))
        for name, l in data.leaf_names(cfg, tp)])


def change_norms(cfg, tp, layers, key):
    """Norm of each leaf's change from the initial weights of the seed
    whose ``data.root_key`` is ``key``."""
    init = data.init_params(cfg, tp, key, jnp.float32)["layers"]
    return leaf_norms(cfg, tp, {k: a.astype(jnp.float32) - init[k]
                                for k, a in layers.items()})


def adamw(opt, lr, p, m, v, g, t):
    b1, b2 = opt["b1"], opt["b2"]
    c1, c2 = ((1 - b ** t).astype(p.dtype) for b in (b1, b2))
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    u = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
    return p - lr * (u + opt["weight_decay"] * p), m, v


def first_steps(cfg, tp, seed, batches, *, dtype=jnp.float32,
                precision="highest", layer=None):
    """Train from the seed's weights for ``len(batches)`` steps.

    Returns the numbers the comparison reads: each step's loss, the
    first step's gradient norm per leaf, and each leaf's change after
    the last step.  One jitted call per step, the state donated, so the
    peak is the weights, AdamW's two moments and one gradient."""
    opt = cfg["optimizer"]
    lr = opt["lr_times_width"] / cfg["ffn_width"]

    def step(layers, m, v, x, y, t):
        loss, g = jax.value_and_grad(
            lambda q: loss_fn(cfg, q, x, y, layer))(layers)
        out = {k: adamw(opt, lr, layers[k], m[k], v[k], g[k], t)
               for k in layers}
        return ({k: o[0] for k, o in out.items()},
                {k: o[1] for k, o in out.items()},
                {k: o[2] for k, o in out.items()},
                loss.astype(jnp.float32), leaf_norms(cfg, tp, g))

    with jax.default_matmul_precision(precision):
        key = data.root_key(seed)
        init = jax.jit(lambda k: data.init_params(cfg, tp, k, dtype))
        jstep = jax.jit(step, donate_argnums=(0, 1, 2))
        layers = init(key)["layers"]
        m = jax.tree.map(jnp.zeros_like, layers)
        v = jax.tree.map(jnp.zeros_like, layers)
        losses = []
        for s, (x, y) in enumerate(batches):
            layers, m, v, loss, gn = jstep(layers, m, v, x.astype(dtype),
                                           y.astype(dtype),
                                           jnp.float32(s + 1))
            losses.append(loss)
            if s == 0:
                grad_norms = gn
        del m, v
        change = jax.jit(lambda q, k: change_norms(cfg, tp, q, k))(layers,
                                                                   key)
    return {"losses": [float(a) for a in losses],
            "grad_norms": [float(a) for a in grad_norms],
            "change_norms": [float(a) for a in change]}
