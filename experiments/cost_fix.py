"""Scan-aware cost correction for the dry-run roofline numbers.

XLA's ``cost_analysis()`` counts a while/scan BODY once, not per trip —
so the layer-scan, grad-accumulation scan, kv-chunk scan, loss-chunk scan
and SSD chunk scan all undercount FLOPs/bytes/collectives.  This pass
recomputes exact per-device totals per cell by:

  * building analysis variants with every inner scan unrolled
    (microbatches=1, attn_kv_chunk=-1, loss_chunk=S, ssd chunk=S) and the
    layer stack at g=1 and g=2 groups,
  * extrapolating linearly in g (costs are affine in the group count:
    intercept = embed/loss/head, slope = per-group cost),

then rewrites flops/bytes/wire + roofline terms in the cell's JSON
(memory_analysis of the REAL full compile is kept — buffers are reused
across scan iterations, so the full compile is the fits proof).

Run AFTER the main sweep:  PYTHONPATH=src python experiments/cost_fix.py
"""
import os
import sys

# a CPU-only tool, and so are the dry-run children that inherit this
# environment: never take the chip
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses
import glob
import json
import subprocess


def fix_one(path: str, timeout: int = 1800) -> bool:
    with open(path) as f:
        rec = json.load(f)
    if rec.get("skipped") or rec.get("cost_method") == "scan-extrapolated":
        return False
    if rec.get("mesh", {}).get("pod"):
        return False            # roofline table is single-pod only
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
        + os.pathsep + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", rec["arch"], "--shape", rec["shape"],
           "--impl", rec["impl"], "--cost-fix", path]
    r = subprocess.run(cmd, capture_output=True, text=True,
                       timeout=timeout, env=env)
    if r.returncode != 0:
        print(f"FAIL {path}\n{r.stdout[-1500:]}\n{r.stderr[-1500:]}")
        return False
    print(r.stdout.strip().splitlines()[-1])
    return True


def main():
    paths = sorted(glob.glob(os.path.join(os.path.dirname(__file__),
                                          "dryrun", "*_sp.json")))
    for p in paths:
        try:
            fix_one(p)
        except Exception as e:
            print(f"ERROR {p}: {e}")
    print("COST FIX DONE")


if __name__ == "__main__":
    main()
