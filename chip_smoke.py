#!/usr/bin/env python3
"""Chip smoke run: the paper-FFN training path and its fused Pallas
kernels on a TPU, at published widths, through the normal entry points.

  python3 chip_smoke.py                # one chip: phases a, b, c
  python3 chip_smoke.py --four-chips   # four chips: the p=4 phase only

Phases (each a function of its sizes, so tests/test_chip_smoke.py runs
them tiny on a CPU mesh; only ``main`` checks the platform and picks the
full sizes):

  a  train paper-ffn-16k (n=16384, L=2) with dense projections on a 1x1
     mesh: AdamW, batch 256, 5 steps through ``make_ffn_train_step``;
     the step-0 loss is checked against a plain jnp f32 forward
  b  ``phantom_fused_linear`` forward and ``jax.grad``, compiled, f32 and
     bf16, at the paper-ffn-16k p=4 per-rank shapes, vs kernels/ref.py
  c  ``flash_attention`` at chatglm3-6b's per-chip shape (8 query heads,
     2 KV heads, head dim 128, S=2048, causal) vs ``flash_attention_ref``
  --four-chips: tensor p=4 on a 1x4 mesh vs the same weights unsharded on
     one chip, and phantom p=4 with the Pallas kernels vs the XLA path,
     run with the TPU comm/compute overlap recipe applied

Lines before the last are smoke numbers (host wall time of a short cold
run and counts from the compiled HLO), not benchmarks.  The last line is
the JSON result.  Every failed check raises, so the exit code is
non-zero and no result line is printed.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
if not os.path.isdir(os.path.join(SRC, "repro")):
    sys.exit(f"chip_smoke: no repro package under {SRC}; run from a "
             f"checkout of the repository")
sys.path.insert(0, SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import (dense_projection_map,  # noqa: E402
                                with_kernel_backend)
from repro.configs.paper_ffn import config as paper_ffn_config  # noqa: E402
from repro.core.ffn import init_ffn, make_ffn_train_step  # noqa: E402
from repro.data.synthetic import TeacherDataset  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.ops import phantom_fused_linear  # noqa: E402
from repro.kernels.ref import (flash_attention_ref,  # noqa: E402
                               phantom_fused_ref)
from repro.launch.hlo_analysis import collective_bytes  # noqa: E402
from repro.optim import AdamW  # noqa: E402

# --- tolerances -----------------------------------------------------------
# On TPU an f32 matmul at default precision rounds its operands to bf16
# (8 significant bits, relative rounding <= 2**-9 each), and accumulates
# in f32.  Every comparison below is a relative error of O(1)-sized
# values, so bf16 operand rounding bounds it by a few 2**-8 ~ 4e-3; the
# limits leave ~5x on top of that, while a wrong weight, layout, mask or
# kernel tile moves the compared value by O(1).
LOSS_RTOL = 2e-2            # step-0 loss vs the plain f32 forward
KERNEL_RTOL = {"float32": 1e-2, "bfloat16": 2e-2}   # + bf16 output rounding
# tensor p=4 vs unsharded, and phantom Pallas vs XLA: the same
# bf16-rounded operands (a Pallas f32 dot at default precision rounds as
# XLA's does) summed in another order -> f32-level loss differences.
# AdamW's first updates are ~lr*sign(g), so the rare entries whose
# gradient sign sits inside that noise flip their update.
P4_LOSS_RTOL, P4_UPDATE_RTOL = 1e-3, 1e-2


class SmokeFailure(AssertionError):
    """A smoke check that did not hold."""


def check(ok: bool, msg: str):
    if not ok:
        raise SmokeFailure(msg)


def rel_err(a, b) -> float:
    """||a - b||_F / ||b||_F in f32."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def host_rel_err(a, b) -> float:
    """``rel_err`` over whole host pytrees (float64 accumulation)."""
    num = den = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        d = np.asarray(x, np.float64) - np.asarray(y, np.float64)
        num += float(np.vdot(d, d))
        den += float(np.vdot(np.asarray(y, np.float64),
                             np.asarray(y, np.float64)))
    return (num / den) ** 0.5


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


# --- the training path ----------------------------------------------------

def dense_cfg(cfg):
    return cfg.replace(projections=dense_projection_map())


def lr_for(width: int) -> float:
    """AdamW's first steps move every weight by ~lr, and a unit sums
    ``width`` of them, so the output change scales with lr*width: a
    fixed lr*width keeps the first steps equally gentle at every width
    (0.3 / width decreases the teacher loss steadily from n=1k to 4k)."""
    return 0.3 / width


def reference_loss(layers, x, y):
    """Plain f32 forward of the dense paper FFN on global arrays:
    h <- relu(h @ w + b) per layer, then mean squared error.  Written
    against the parameter arrays only, not the sharded step code."""
    h = x
    for i in range(layers["w"].shape[0]):
        h = jnp.dot(h, layers["w"][i], precision=jax.lax.Precision.HIGHEST)
        h = jax.nn.relu(h + layers["b"][i])
    return jnp.mean(jnp.square(h - y))


def train(cfg, mesh, *, batch: int, steps: int, lr: float, seed: int = 0,
          on_init=None):
    """Train ``cfg`` for ``steps`` on ``mesh`` through the normal entry
    points (``init_ffn`` + ``make_ffn_train_step`` + ``TeacherDataset``).

    ``on_init(params, x0, y0)`` runs before the first (donating) step.
    Returns a dict: losses, per-step wall seconds, compile seconds, the
    compiled HLO text, final params on the host, and on_init's result."""
    opt = AdamW(lr, weight_decay=0.0)
    step_fn, _, _ = make_ffn_train_step(cfg, mesh, opt, batch)
    params, opt_state = init_ffn(cfg, mesh, opt, seed=seed)
    ds = TeacherDataset(cfg.ffn_width, batch, seed=seed)
    x, y = ds(0)
    t0 = time.perf_counter()
    compiled = step_fn.lower(params, opt_state, jnp.int32(0), x, y).compile()
    compile_s = time.perf_counter() - t0
    in_sh = compiled.input_shardings[0]
    params, opt_state = jax.device_put((params, opt_state), in_sh[:2])
    init = on_init(params, x, y) if on_init else None

    losses, walls = [], []
    for s in range(steps):
        if s:
            x, y = ds(s)
        x, y = jax.device_put((x, y), in_sh[3:])
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state,
                                           jnp.int32(s), x, y)
        loss = float(jax.block_until_ready(loss))
        walls.append(time.perf_counter() - t0)
        losses.append(loss)
    return {"losses": losses, "walls": walls, "compile_s": compile_s,
            "hlo": compiled.as_text(), "params": jax.device_get(params),
            "init": init}


def train_phase(cfg, mesh, *, batch: int, steps: int, seed: int = 0):
    """(a) dense paper-FFN training: finite losses, step-0 loss within
    ``LOSS_RTOL`` of ``reference_loss``, last loss below the first."""
    cfg = dense_cfg(cfg)
    r = train(cfg, mesh, batch=batch, steps=steps, lr=lr_for(cfg.ffn_width),
              seed=seed,
              on_init=lambda p, x, y: float(reference_loss(p["layers"],
                                                           x, y)))
    losses, ref = r["losses"], r["init"]
    err = abs(losses[0] - ref) / abs(ref)
    log(f"a train {cfg.name} n={cfg.ffn_width} L={cfg.num_layers} "
        f"batch={batch}: losses {losses}")
    log(f"a step-0 loss {losses[0]!r} vs plain f32 forward {ref!r}: "
        f"rel err {err:.3e} (limit {LOSS_RTOL})")
    log(f"a smoke wall: compile {r['compile_s']:.2f} s, steps "
        f"{[round(w, 4) for w in r['walls']]} s")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(err <= LOSS_RTOL, f"step-0 loss {losses[0]} vs reference {ref}: "
          f"rel err {err:.3e} > {LOSS_RTOL}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    return r


# --- the kernels ------------------------------------------------------------

def _compile(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    return c, c.as_text()


def fused_phase(*, M: int, K: int, N: int, PK: int, dtypes, interpret: bool,
                seed: int = 0):
    """(b) ``phantom_fused_linear`` forward and grads vs kernels/ref.py
    (reference at full f32 matmul precision)."""
    for dt in dtypes:
        name = jnp.dtype(dt).name
        ks = jax.random.split(jax.random.key(seed), 5)
        x = jax.random.normal(ks[0], (M, K), dt)
        L = (jax.random.normal(ks[1], (K, N)) * K ** -0.5).astype(dt)
        g = jax.random.normal(ks[2], (M, PK), dt)
        D = (jax.random.normal(ks[3], (PK, N)) * PK ** -0.5).astype(dt)
        ct = jax.random.normal(ks[4], (M, N), jnp.float32)

        def kern(x, L, g, D):
            return phantom_fused_linear(x, L, g, D, interpret=interpret)

        def obj(f):
            return lambda *a: jnp.sum(f(*a).astype(jnp.float32) * ct)

        fwd, fwd_txt = _compile(kern, x, L, g, D)
        grad, grad_txt = _compile(jax.grad(obj(kern), argnums=(0, 1, 2, 3)),
                                  x, L, g, D)
        z, grads = fwd(x, L, g, D), grad(x, L, g, D)
        with jax.default_matmul_precision("float32"):
            z_ref = phantom_fused_ref(x, L, g, D)
            g_ref = jax.grad(obj(phantom_fused_ref),
                             argnums=(0, 1, 2, 3))(x, L, g, D)
        errs = {"z": rel_err(z, z_ref)}
        errs.update({f"d{n}": rel_err(a, b)
                     for n, a, b in zip("xLgD", grads, g_ref)})
        tol = KERNEL_RTOL[name]
        log(f"b phantom_fused_linear {name} M={M} K={K} N={N} PK={PK}: "
            + ", ".join(f"{k} {v:.3e}" for k, v in errs.items())
            + f" (limit {tol})")
        check(all(np.isfinite(v) for v in errs.values()),
              f"non-finite error in {errs}")
        bad = {k: v for k, v in errs.items() if v > tol}
        check(not bad, f"fused phantom {name}: {bad} over {tol}")
        if not interpret:
            check("tpu_custom_call" in fwd_txt
                  and "tpu_custom_call" in grad_txt,
                  f"fused phantom {name}: no tpu_custom_call in the "
                  f"compiled forward/grad")


def flash_phase(*, B: int, S: int, H: int, KV: int, hd: int, dtypes,
                interpret: bool, seed: int = 0):
    """(c) causal GQA ``flash_attention`` vs ``flash_attention_ref``."""
    for dt in dtypes:
        name = jnp.dtype(dt).name
        ks = jax.random.split(jax.random.key(seed), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), dt)
        k = jax.random.normal(ks[1], (B, S, KV, hd), dt)
        v = jax.random.normal(ks[2], (B, S, KV, hd), dt)
        fn, txt = _compile(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=interpret), q, k, v)
        out = fn(q, k, v)
        with jax.default_matmul_precision("float32"):
            ref = flash_attention_ref(q, k, v, causal=True)
        err, tol = rel_err(out, ref), KERNEL_RTOL[name]
        log(f"c flash_attention {name} B={B} S={S} H={H} KV={KV} hd={hd}: "
            f"rel err {err:.3e} (limit {tol})")
        check(np.isfinite(err) and err <= tol,
              f"flash {name}: rel err {err} over {tol}")
        if not interpret:
            check("tpu_custom_call" in txt,
                  f"flash {name}: no tpu_custom_call in the compiled program")


# --- four chips -------------------------------------------------------------

def _updates(run):
    return jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b,
                        run["params"], run["init"])


def _compare(tag, a, b, loss_rtol, update_rtol):
    la, lb = np.asarray(a["losses"]), np.asarray(b["losses"])
    loss_err = float(np.max(np.abs(la - lb) / np.abs(lb)))
    upd_err = host_rel_err(_updates(a), _updates(b))
    log(f"{tag}: losses {la.tolist()} vs {lb.tolist()}")
    log(f"{tag}: max loss rel err {loss_err:.3e} (limit {loss_rtol}), "
        f"update rel err {upd_err:.3e} (limit {update_rtol})")
    check(np.all(np.isfinite(la)) and np.all(np.isfinite(lb)),
          f"{tag}: non-finite loss")
    check(loss_err <= loss_rtol, f"{tag}: loss rel err {loss_err}")
    check(upd_err <= update_rtol, f"{tag}: update rel err {upd_err}")


def _wire(run, p):
    return collective_bytes(run["hlo"], default_group=p)[0]


def four_chip_phase(cfg, mesh_p, mesh_1, *, batch: int, steps: int,
                    interpret: bool, seed: int = 0):
    """Tensor p on ``mesh_p`` vs the same weights unsharded on ``mesh_1``;
    phantom p with ``kernel_backend="pallas"`` vs ``"xla"`` on
    ``mesh_p``, from the same init.  Prints each step's collective wire
    bytes from the compiled HLO."""
    p = mesh_p.shape["model"]
    lr = lr_for(cfg.ffn_width)
    host_init = (lambda prm, x, y: jax.device_get(prm))
    kw = dict(batch=batch, steps=steps, lr=lr, seed=seed)

    dense = dense_cfg(cfg)
    tp = train(dense, mesh_p, on_init=host_init, **kw)
    one = train(dense, mesh_1, on_init=host_init, **kw)
    _compare(f"tensor p={p} vs unsharded", tp, one,
             P4_LOSS_RTOL, P4_UPDATE_RTOL)
    del one

    ph_xla = train(with_kernel_backend(cfg, "xla"), mesh_p,
                   on_init=host_init, **kw)
    ph_pl = train(with_kernel_backend(cfg, "pallas"), mesh_p,
                  on_init=host_init, **kw)
    _compare(f"phantom p={p} pallas vs xla", ph_pl, ph_xla,
             P4_LOSS_RTOL, P4_UPDATE_RTOL)
    if not interpret:
        check("tpu_custom_call" in ph_pl["hlo"],
              "phantom pallas step: no tpu_custom_call in the compiled step")

    # XLA may narrow the ghost all-gather to bf16 ahead of its
    # default-precision dot; the kernel takes the ghosts in f32, so the
    # two phantom counts need not agree
    k = cfg.phantom.k
    w_tp, w_ph, w_pl = _wire(tp, p), _wire(ph_xla, p), _wire(ph_pl, p)
    log(f"wire bytes per device in the compiled step (an op in the layer "
        f"loop counted once): tensor {w_tp:.0f}, phantom xla {w_ph:.0f}, "
        f"phantom pallas {w_pl:.0f}; phantom xla/tensor "
        f"{w_ph / w_tp:.4g} vs k*p/n = {k * p / cfg.ffn_width:.4g}")
    for tag, r in (("tensor", tp), ("phantom xla", ph_xla),
                   ("phantom pallas", ph_pl)):
        log(f"smoke wall {tag} p={p}: compile {r['compile_s']:.2f} s, "
            f"steps {[round(w, 4) for w in r['walls']]} s")
    check(max(w_ph, w_pl) < w_tp,
          f"phantom wire bytes {w_ph}/{w_pl} not below tensor {w_tp}")


# --- entry point --------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the p=4 phase; needs exactly 4 TPUs")
    args = ap.parse_args(argv)

    if args.four_chips:
        # libtpu reads its flags when the backend starts: before devices()
        from repro.parallel.compat import enable_comm_overlap
        applied = enable_comm_overlap("tpu")
        log(f"overlap recipe in LIBTPU_INIT_ARGS: {applied or '(already)'}")

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform} "
              f"({len(devs)} device(s))", file=sys.stderr)
        return 2
    if args.four_chips and len(devs) != 4:
        print(f"chip_smoke: --four-chips needs exactly 4 TPU devices; "
              f"found {len(devs)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_local_mesh
    log(f"device {devs[0].device_kind} x{len(devs)}, jax {jax.__version__}, "
        f"compile cache {enable_compile_cache()}")

    cfg = paper_ffn_config("paper-ffn-16k")          # n=16384, L=2, k=16
    p, n = 4, cfg.ffn_width
    if args.four_chips:
        four_chip_phase(cfg, make_local_mesh(1, p), make_local_mesh(1, 1),
                        batch=256, steps=3, interpret=False)
    else:
        train_phase(cfg, make_local_mesh(1, 1), batch=256, steps=5)
        # per-rank shapes of paper-ffn-16k at p=4: K = N = n/p, PK = p*k
        fused_phase(M=256, K=n // p, N=n // p, PK=p * cfg.phantom.k,
                    dtypes=(jnp.float32, jnp.bfloat16), interpret=False)
        # chatglm3-6b on one chip of four: 32/4 query heads, 2 KV heads
        flash_phase(B=1, S=2048, H=8, KV=2, hd=128,
                    dtypes=(jnp.float32, jnp.bfloat16), interpret=False)

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
