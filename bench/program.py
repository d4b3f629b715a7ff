"""The system under test: the program's paper-FFN train step.

The benchmark takes from the program only this: ``make_ffn_train_step``
with the program's ``AdamW``, built for the configuration a cell names,
and its parameter layout.  The weights and batches come from
``bench/data.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


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
