import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# a CPU-only tool, and so are the --all children that inherit this
# environment: never take the chip from the process that owns it
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ both MUST precede any jax import: jax locks them on first init.

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape x mesh) cell this lowers + compiles
the real step function — train_step (fwd+bwd+optimizer) for train shapes,
forward_prefill for prefill shapes, forward_decode (one token against a
seq_len KV cache) for decode shapes — against ShapeDtypeStruct stand-ins
on the production mesh, then records:

  * compiled.memory_analysis()   (per-device bytes: proves it fits)
  * compiled.cost_analysis()     (per-device FLOPs / HBM bytes)
  * collective wire bytes parsed from the optimized HLO
  * the three roofline terms (DESIGN.md §7)

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-14b --shape train_4k
  python -m repro.launch.dryrun --arch qwen2.5-14b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all          # every cell, subprocesses
"""

import argparse
import dataclasses
import json
import subprocess
import sys
import time


def make_cfg(arch: str, impl: str, variant: str | None = None,
             extra: dict | None = None):
    from repro.configs.base import get_config
    extra = dict(extra or {})
    nested = {k.split(".", 1)[1]: extra.pop(k)
              for k in list(extra) if k.startswith("phantom.")}
    cfg = get_config(arch, **extra)
    if nested:
        from repro.configs.base import with_phantom_overrides
        cfg = with_phantom_overrides(cfg, **nested)
    if impl == "dense":
        from repro.configs.base import dense_projection_map
        cfg = cfg.replace(projections=dense_projection_map())
    elif variant:
        from repro.configs.base import with_phantom_overrides
        cfg = with_phantom_overrides(cfg, variant=variant)
    return cfg


def analysis_cfg(cfg, shape, groups: int):
    """Variant for exact cost accounting: every inner scan unrolled
    (XLA counts scan bodies once) and `groups` layer groups."""
    from repro.models.blocks import plan_period
    over = dict(microbatches=1, attn_kv_chunk=-1,
                loss_chunk=shape.seq_len, scan_layers=False)
    if cfg.family == "encdec":
        over["encoder_layers"] = groups
        over["num_layers"] = groups
    else:
        over["num_layers"] = plan_period(cfg) * groups
    if cfg.ssm is not None:
        over["ssm"] = dataclasses.replace(cfg.ssm,
                                          chunk=max(shape.seq_len, 16))
    return cfg.replace(**over)


def build_and_compile(arch: str, shape_name: str, multi_pod: bool,
                      impl: str, variant: str | None = None,
                      extra: dict | None = None, cfg=None):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import SHAPES, get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import cache_specs, input_specs
    from repro.models.model import model_decls
    from repro.optim import make_optimizer
    from repro.parallel.axes import MeshAxes, resolve_spec
    from repro.parallel.params import abstract, specs

    if cfg is None:
        cfg = make_cfg(arch, impl, variant, extra)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = MeshAxes.from_mesh(mesh)

    t0 = time.time()
    if shape.kind == "train":
        from repro.train.trainer import make_train_step
        opt = make_optimizer(cfg.optimizer, 3e-4, weight_decay=0.1)
        step, decls, opt_decls = make_train_step(
            cfg, mesh, opt, batch_spec=input_specs(cfg, shape, axes)[1],
            microbatches=cfg.microbatches)
        params = abstract(decls)
        opt_state = abstract(opt_decls)
        batch_sds, _ = input_specs(cfg, shape, axes)
        import jax.numpy as jnp
        args = (params, opt_state,
                jax.ShapeDtypeStruct((), jnp.int32), batch_sds)
        lowered = step.lower(*args)
    else:
        from repro.serve.engine import make_serve_fns
        prefill_fn, decode_fn, cache_sds, _cspecs = make_serve_fns(
            cfg, mesh, shape)
        decls = model_decls(cfg, axes)
        params = abstract(decls)
        import jax.numpy as jnp
        if shape.kind == "prefill":
            batch_sds, _ = input_specs(cfg, shape, axes)
            lowered = prefill_fn.lower(params, batch_sds)
        else:
            B = shape.global_batch
            toks = jax.ShapeDtypeStruct((B, 1), jnp.int32)
            pos = jax.ShapeDtypeStruct((B,), jnp.int32)
            lowered = decode_fn.lower(params, cache_sds, toks, pos)
    t_lower = time.time() - t0

    t1 = time.time()
    # compile through the shared telemetry cache: a module lowered to
    # identical HLO (cost-fix g=1/g=2 reruns, planner HBM-fit checks)
    # is compiled once per process; ``analyze`` parses it through the
    # matching analysis cache.  Only the compile is timed here — a ~0
    # compile_s means this process genuinely didn't recompile.
    from repro.telemetry import compile_lowered
    compiled = compile_lowered(lowered)
    t_compile = time.time() - t1
    return cfg, mesh, compiled, {"lower_s": t_lower, "compile_s": t_compile}


def analyze(cfg, mesh, compiled, timings, shape_name: str, impl: str):
    from repro.core.energy import roofline_terms
    from repro.models.model import count_params
    from repro.telemetry import analyze_compiled

    tp = mesh.shape["model"]
    costs = analyze_compiled(compiled, default_group=tp)
    flops = costs.flops
    hbm_bytes = costs.hbm_bytes
    wire, breakdown = costs.collective_wire_bytes, costs.collectives
    mem = costs.memory
    rt = roofline_terms(flops, hbm_bytes, wire)

    from repro.configs.base import SHAPES
    shape = SHAPES[shape_name]
    n_active = count_params(cfg, active_only=True, tp=tp)
    n_total = count_params(cfg, active_only=False, tp=tp)
    tokens = shape.global_batch * (shape.seq_len if shape.kind == "train"
                                   else 1)
    mf = 6.0 * n_active * tokens
    if shape.kind != "train":
        mf = 2.0 * n_active * tokens       # inference: fwd only
    n_dev = mesh.devices.size
    model_flops_per_dev = mf / n_dev

    return {
        "arch": cfg.name, "shape": shape_name, "impl": impl,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "devices": int(n_dev),
        "flops_per_device": flops,
        "hbm_bytes_per_device": hbm_bytes,
        "collective_wire_bytes_per_device": wire,
        "collectives": breakdown,
        "memory": mem,
        "roofline": {
            "compute_s": rt.compute_s, "memory_s": rt.memory_s,
            "collective_s": rt.collective_s, "dominant": rt.dominant,
            "step_s": rt.step_s,
            "fraction": rt.fraction_of_roofline(),
        },
        "params_total": n_total, "params_active": n_active,
        "model_flops_per_device": model_flops_per_dev,
        "useful_flops_ratio": (model_flops_per_dev / flops) if flops else 0,
        "timings": timings,
    }


def _cell_costs(compiled, tp):
    from repro.telemetry import analyze_compiled
    c = analyze_compiled(compiled, default_group=tp)
    return (c.flops, c.hbm_bytes, c.collective_wire_bytes, c.collectives)


def parse_sets(pairs):
    """--set key=value (typed) -> cfg override dict."""
    out = {}
    for pair in pairs or []:
        k, v = pair.split("=", 1)
        if v in ("true", "True"):
            out[k] = True
        elif v in ("false", "False"):
            out[k] = False
        else:
            try:
                out[k] = int(v)
            except ValueError:
                out[k] = v
    return out


def cost_fix(arch, shape_name, impl, json_path, variant=None,
             overrides=None):
    """Scan-aware exact cost totals via g=1 / g=2 extrapolation (see
    experiments/cost_fix.py docstring); rewrites the cell JSON."""
    from repro.configs.base import SHAPES
    from repro.core.energy import roofline_terms
    from repro.models.blocks import plan_period
    from repro.models.model import count_params

    if os.path.exists(json_path):
        with open(json_path) as f:
            rec = json.load(f)
    else:
        rec = {"arch": arch, "shape": shape_name, "impl": impl,
               "mesh": {"data": 16, "model": 16}, "devices": 256,
               "memory": {}, "overrides": overrides or {}}
    cfg = make_cfg(arch, impl, variant, extra=overrides)
    shape = SHAPES[shape_name]
    base = {}
    for g in (1, 2):
        cfg_g = analysis_cfg(cfg, shape, g)
        _c, mesh, compiled, _t = build_and_compile(
            arch, shape_name, False, impl, cfg=cfg_g)
        base[g] = _cell_costs(compiled, mesh.shape["model"])
    if cfg.family == "encdec":
        n_groups = cfg.num_layers
    else:
        n_groups = cfg.num_layers // plan_period(cfg)
    f1, b1, w1, _ = base[1]
    f2, b2, w2, bd2 = base[2]
    flops = f1 + (f2 - f1) * (n_groups - 1)
    hbm = b1 + (b2 - b1) * (n_groups - 1)
    wire = w1 + (w2 - w1) * (n_groups - 1)
    # scale the per-op breakdown by the same wire ratio for reporting
    scale = wire / max(w2, 1e-9)
    breakdown = {k: {"count": v["count"],
                     "result_bytes": v["result_bytes"],
                     "wire_bytes": v["wire_bytes"] * scale}
                 for k, v in bd2.items()}

    rt = roofline_terms(flops, hbm, wire)
    tp = 16
    n_active = count_params(cfg, active_only=True, tp=tp)
    tokens = shape.global_batch * (shape.seq_len if shape.kind == "train"
                                   else 1)
    mf = (6.0 if shape.kind == "train" else 2.0) * n_active * tokens
    model_flops_per_dev = mf / 256
    rec.update({
        "flops_per_device": flops,
        "hbm_bytes_per_device": hbm,
        "collective_wire_bytes_per_device": wire,
        "collectives": breakdown,
        "roofline": {
            "compute_s": rt.compute_s, "memory_s": rt.memory_s,
            "collective_s": rt.collective_s, "dominant": rt.dominant,
            "step_s": rt.step_s, "fraction": rt.fraction_of_roofline(),
        },
        "model_flops_per_device": model_flops_per_dev,
        "useful_flops_ratio": (model_flops_per_dev / flops) if flops else 0,
        "cost_method": "scan-extrapolated",
    })
    with open(json_path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"fixed {json_path}: frac={rec['roofline']['fraction']:.3f} "
          f"dom={rec['roofline']['dominant']}")
    return rec


def run_cell(arch, shape, multi_pod, impl, variant=None, out_path=None,
             print_hlo_ops=False):
    cfg, mesh, compiled, timings = build_and_compile(
        arch, shape, multi_pod, impl, variant)
    rec = analyze(cfg, mesh, compiled, timings, shape, impl)
    print(compiled.memory_analysis())
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):     # older jax: one dict per device
        ca = ca[0] if ca else {}
    print({k: v for k, v in sorted(ca.items())
           if k in ("flops", "bytes accessed")})
    print(json.dumps(rec["roofline"], indent=None))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"wrote {out_path}")
    return rec


SKIP = {
    # long_500k needs sub-quadratic attention: full-attention archs skip
    # (DESIGN.md §5); mamba2/jamba run it.
    ("granite-moe-3b-a800m", "long_500k"),
    ("olmoe-1b-7b", "long_500k"),
    ("seamless-m4t-large-v2", "long_500k"),
    ("chatglm3-6b", "long_500k"),
    ("qwen2.5-14b", "long_500k"),
    ("stablelm-3b", "long_500k"),
    ("phi3-mini-3.8b", "long_500k"),
    ("qwen2-vl-72b", "long_500k"),
}


def run_all(out_dir: str, impls=("dense", "phantom"), multi_pods=(False,),
            archs=None, shapes=None, timeout: int = 3600):
    from repro.configs.base import ARCH_IDS, SHAPES
    os.makedirs(out_dir, exist_ok=True)
    archs = archs or ARCH_IDS
    shapes = shapes or list(SHAPES)
    results = []
    for arch in archs:
        for shape in shapes:
            for impl in impls:
                for mp in multi_pods:
                    tag = f"{arch}_{shape}_{impl}_{'mp' if mp else 'sp'}"
                    out = os.path.join(out_dir, tag + ".json")
                    if (arch, shape) in SKIP:
                        with open(out, "w") as f:
                            json.dump({"arch": arch, "shape": shape,
                                       "impl": impl, "skipped":
                                       "full-attention arch at 500k"}, f)
                        print(f"SKIP {tag}")
                        continue
                    if os.path.exists(out):
                        print(f"CACHED {tag}")
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape,
                           "--impl", impl, "--out", out]
                    if mp:
                        cmd.append("--multi-pod")
                    print(f"RUN {tag}", flush=True)
                    env = dict(os.environ)
                    src = os.path.join(os.path.dirname(os.path.dirname(
                        os.path.dirname(os.path.dirname(
                            os.path.abspath(__file__))))), "src")
                    env["PYTHONPATH"] = (src + os.pathsep
                                         + env.get("PYTHONPATH", ""))
                    r = subprocess.run(cmd, capture_output=True, text=True,
                                       timeout=timeout, env=env)
                    if r.returncode != 0:
                        print(f"FAIL {tag}\n{r.stdout[-2000:]}"
                              f"\n{r.stderr[-2000:]}")
                    else:
                        print(r.stdout.strip().splitlines()[-1])
                    results.append((tag, r.returncode))
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--shape", default="train_4k",
                    choices=["train_4k", "prefill_32k", "decode_32k",
                             "long_500k"])
    ap.add_argument("--impl", default="phantom",
                    choices=["dense", "phantom"])
    ap.add_argument("--variant", default=None,
                    choices=[None, "faithful", "fused", "ring"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--cost-fix", default=None,
                    help="path to a cell JSON to rewrite with "
                         "scan-extrapolated exact costs")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (repeatable); used by "
                         "the §Perf hillclimb")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    overrides = parse_sets(getattr(args, "set"))
    if args.cost_fix:
        cost_fix(args.arch, args.shape, args.impl, args.cost_fix,
                 args.variant, overrides=overrides)
        return
    if args.all:
        run_all(args.out_dir)
        return
    run_cell(args.arch, args.shape, args.multi_pod, args.impl,
             args.variant, args.out)


if __name__ == "__main__":
    main()
