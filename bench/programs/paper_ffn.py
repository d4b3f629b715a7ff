"""The paper FFN (arXiv 2508.00960 §VI) as the benchmark trains it.

A configuration file names its program (``"program": "paper_ffn"``) and
the harness reaches everything below through that name alone
(``bench/spec.py`` lists the interface):

  system under test  ``build``: the program's ``make_ffn_train_step``
                     with its ``AdamW``, compiled for the cell
  inputs             ``init_params`` and ``batch_pool``: weights and the
                     Gaussian-teacher batches, made on the device from
                     the seed's ``data.root_key``; the layouts follow the
                     parameter tree the program's ``init_ffn`` declares
                     (``{"layers": {leaf: [L, ...]}}``), and the harness
                     checks that they still agree before a run
  plain reference    ``first_steps``: the layer equations, importing
                     nothing of ``repro``, in float32 at ``highest``
                     matmul precision, as the configurations state their
                     parameters in float32; the same code in bfloat16 is
                     the control that the comparison has to fail
                     (``bench/calibrate.py``), and ``no_exchange`` plants
                     the fault of a step that leaves the chips' exchange
                     out
  operations         ``step_flops`` and ``matmul_floor_s``, from shapes

The reference's equations:

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
import numpy as np

from bench import data

FIRST_STEPS = 3        # steps the comparison follows
TINY = {"ffn_width": 128, "k": 4}       # the CPU tests' size


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def model_config(cfg: dict):
    """The program's ModelConfig for a bench configuration file."""
    from repro.configs.base import dense_projection_map, with_phantom_overrides
    from repro.configs.paper_ffn import config

    n, L = cfg["ffn_width"], cfg["num_layers"]
    mc = config(cfg["program_config"]).replace(
        d_model=n, ffn_width=n, num_layers=L, ffn_depth=L,
        mlp=cfg["activation"])
    if cfg["projection"] == "tensor":
        return mc.replace(projections=dense_projection_map())
    mc = with_phantom_overrides(mc, **cfg["phantom"])
    spec = mc.projection_spec("ffn_layer")
    got = {key: getattr(spec, key) for key in cfg["phantom"]}
    if spec.kind != "phantom" or got != cfg["phantom"]:
        raise ValueError(f"program runs {spec.kind} {got}, not the "
                         f"phantom {cfg['phantom']} of {cfg['name']}")
    return mc


def mesh_tp(cfg: dict, chips: int) -> int:
    tp = cfg["tp"] or chips
    if tp != chips:
        raise ValueError(f"{cfg['name']} runs on tp={tp} chips, the cell "
                         f"asks for {chips}")
    return tp


class Program:
    """The compiled train step of one cell, and the shardings it takes."""

    def __init__(self, cfg: dict, batch: int, mesh):
        from repro.core.ffn import make_ffn_train_step
        from repro.optim import AdamW
        from repro.parallel.params import abstract

        o = cfg["optimizer"]
        self.b1 = o["b1"]
        self.opt = AdamW(o["lr_times_width"] / cfg["ffn_width"], b1=o["b1"],
                         b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"])
        step, decls, opt_decls = make_ffn_train_step(
            model_config(cfg), mesh, self.opt, batch)
        n = cfg["ffn_width"]
        xs = jax.ShapeDtypeStruct((batch, n), jnp.float32)
        self.abstract_params = abstract(decls)
        self.compiled = step.lower(
            self.abstract_params, abstract(opt_decls),
            jax.ShapeDtypeStruct((), jnp.int32), xs, xs).compile()
        sh = self.compiled.input_shardings[0]
        self.param_sharding, self.opt_sharding = sh[0], sh[1]
        self.batch_sharding = sh[3]

    def init_opt(self, params):
        return jax.jit(self.opt.init, out_shardings=self.opt_sharding)(params)

    def __call__(self, params, opt_state, step: int, x, y):
        return self.compiled(params, opt_state, np.int32(step), x, y)


def build(cfg: dict, traffic: dict, mesh) -> Program:
    return Program(cfg, traffic["global_batch"], mesh)


def grad_tree(opt_state):
    """The first moment, which after one step is (1 - b1) x the gradient."""
    return opt_state["m"]["layers"]


def param_tree(params):
    return params["layers"]


def tiny_config(cfg: dict) -> dict:
    """The configuration at the size the CPU tests run."""
    out = dict(cfg, ffn_width=TINY["ffn_width"])
    if out["projection"] == "phantom":
        out["phantom"] = dict(out["phantom"], k=TINY["k"])
    return out


# ---------------------------------------------------------------------------
# inputs and weights, from the seed
# ---------------------------------------------------------------------------

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


def batch_pool(cfg: dict, traffic: dict, key) -> list:
    """The paper's Gaussian teacher: ``pool_batches`` batches
    ``(x, y)``, ``x ~ N(0, 1)``, ``y = relu(relu(x) W)`` with one
    ``W ~ N(0, 1/n)`` per seed (``key`` is its ``root_key``).  Call
    under ``jax.jit`` on one device, so that every run of a seed, the
    reference's included, gets the same bits."""
    if traffic["kind"] != "gaussian_teacher":
        raise ValueError(f"paper_ffn takes gaussian_teacher traffic, not "
                         f"{traffic['kind']!r}")
    n, b = cfg["ffn_width"], traffic["global_batch"]
    kw, kx = jax.random.split(jax.random.fold_in(key, 1))
    w = jax.random.normal(kw, (n, n), jnp.float32) * n ** -0.5
    out = []
    for i in range(traffic["pool_batches"]):
        x = jax.random.normal(jax.random.fold_in(kx, i), (b, n), jnp.float32)
        out.append((x, jax.nn.relu(jax.nn.relu(x) @ w)))
    return out


# ---------------------------------------------------------------------------
# the plain reference, on global arrays
# ---------------------------------------------------------------------------

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
    """Norm of each (leaf, layer) in ``leaf_names`` order, in f32."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        layers[name][l].astype(jnp.float32))))
        for name, l in leaf_names(cfg, tp)])


def change_norms(cfg, tp, layers, key):
    """Norm of each leaf's change from the initial weights of the seed
    whose ``data.root_key`` is ``key``."""
    init = init_params(cfg, tp, key, jnp.float32)["layers"]
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
        init = jax.jit(lambda k: init_params(cfg, tp, k, dtype))
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


def no_exchange(cfg: dict, tp: int) -> dict:
    """``first_steps`` keywords for a reference with the exchange between
    the tp ranks left out: rank j sees only its own feature block, so
    tensor's all-gather leaves W block-diagonal and phantom loses its
    ghost terms."""
    if cfg["projection"] == "tensor":
        def layer(h, p):
            w, m = p["w"], p["w"].shape[0] // tp
            diag = jnp.stack([w[j * m:(j + 1) * m, j * m:(j + 1) * m]
                              for j in range(tp)])
            B = h.shape[0]
            z = jnp.einsum("bjm,jmo->bjo", h.reshape(B, tp, -1), diag)
            return z.reshape(B, -1) + p["b"]
        return {"layer": layer}

    def layer(h, p):
        B = h.shape[0]
        x = h.reshape(B, tp, -1)
        return (jnp.einsum("bjm,jmo->bjo", x, p["L"]).reshape(B, -1)
                + p["b"])
    return {"layer": layer}


# ---------------------------------------------------------------------------
# operations and bytes of one training step, from shapes alone
# ---------------------------------------------------------------------------
#
# Counts are per chip and per step, and count only the matrix products
# the step needs: every layer's forward and weight gradient, and every
# input gradient that some weight gradient depends on.  The first layer's
# gradient with respect to the batch is not needed, so it is left out,
# though the step's layer loop may compute it: that is up to a sixth less
# than "6 x batch x parameters" at L = 2.  (Phantom's first layer still
# needs the gradient of its ghosts, which its compressor's gradient
# takes.)
#
# Phantom counts the paper's per-rank terms (``PhantomStrategy.flops``,
# copied here, not imported): the local diagonal block (n/p)^2, the
# compressor (n/p) k and the (p - 1) decompressors k (n/p).  The count is
# the same whatever computes the products (XLA dots or a Pallas kernel).

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


def step_flops(cfg: dict, tp: int, traffic: dict) -> float:
    """Required matmul FLOPs of one step on one chip."""
    return float(sum(2 * m * k * n for m, k, n in
                     gemms(cfg, tp, traffic["global_batch"])))


def matmul_floor_s(cfg: dict, tp: int, traffic: dict, peak: dict) -> float:
    """Least time one chip can spend on the step's products.  Each
    product takes at least the longer of its FLOPs at the bf16 peak and
    its bytes at the HBM peak.  Bytes are the least a product can move:
    each operand read once and the result written once, at 2 bytes an
    element (the bf16 operands that a float32 dot at default precision
    feeds the MXU), so the floor never overstates."""
    return sum(max(2.0 * m * k * n / peak["bf16_flops_per_s"],
                   2.0 * (m * k + k * n + m * n) / peak["hbm_bytes_per_s"])
               for m, k, n in gemms(cfg, tp, traffic["global_batch"]))
