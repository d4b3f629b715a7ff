#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
      --control-seeds 7,8,9 [--out chiprun_out/calibrate-<cell>.json]

Runs on the chip, at the cell's own size, in one process (one compile):
for every seed, the program's first steps against the reference (the
lower readings); for every control seed, the reference in bfloat16 put
in the program's place (the control) and each fault of ``faults.py``
against the reference.  The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def calibrate(cell, seeds, control_seeds, log=print):
    import jax
    import jax.numpy as jnp

    from bench import compare, faults, harness

    prog, tp, devices = harness.build(cell)
    out = {"cell": cell.name, "program": {}, "control": {}, "faults": {}}
    for seed in sorted(set(seeds) | set(control_seeds)):
        t0 = time.perf_counter()
        state, pool, got = harness.first_steps(cell, prog, tp, seed)
        jax.block_until_ready(state)
        del state, pool
        t1 = time.perf_counter()
        ref = harness.reference_steps(cell, tp, seed, devices[0])
        t2 = time.perf_counter()
        if seed in seeds:
            out["program"][seed] = compare.numbers(got, ref)
            log(f"seed {seed} program {out['program'][seed]} "
                f"(program {t1 - t0:.2f} s, reference {t2 - t1:.2f} s)")
        if seed not in control_seeds:
            continue
        ctl = harness.reference_steps(cell, tp, seed, devices[0],
                                      dtype=jnp.bfloat16,
                                      precision="default")
        out["control"][seed] = compare.numbers(ctl, ref)
        log(f"seed {seed} control {out['control'][seed]}")
        fs = {"unchanged": compare.numbers(faults.unchanged(ref), ref),
              "half_batch": compare.numbers(harness.reference_steps(
                  cell, tp, seed, devices[0],
                  rows=cell.traffic["global_batch"] // 2), ref)}
        if cell.chips > 1:
            fs["no_exchange"] = compare.numbers(harness.reference_steps(
                cell, tp, seed, devices[0],
                **cell.program.no_exchange(cell.config, tp)), ref)
        out["faults"][seed] = fs
        log(f"seed {seed} faults {fs}")
    for part in ("program", "control"):
        for k in compare.NUMBERS:
            vals = [v[k] for v in out[part].values()]
            if vals:
                log(f"{part} {k}: max {max(vals)!r} min {min(vals)!r}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from bench import spec
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    cell = spec.load_cell(args.workload)
    out = calibrate(cell, args.seeds, args.control_seeds,
                    log=lambda m: print(f"[calibrate] {m}", flush=True))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
