"""The benchmark finds a new cell, configuration, traffic mix and metric
by name, as files and entries only, and refuses to run off a TPU."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec

ROOT = spec.ROOT


def _copy(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_shipped_benchmark_validates():
    bench = spec.load_benchmark()
    cells = spec.validate()
    assert [c.name for c in cells] == [w["name"] for w in bench["workloads"]]
    for c in cells:
        assert c.per_layer and c.end_to_end
        assert "setup_s" in {m["name"] for m in c.end_to_end}


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_limits_lie_between_their_readings(cell):
    """Each limit sits above the largest sound reading and below the
    least control or fault reading it was set from, with more room
    above the lower."""
    with open(os.path.join(ROOT, spec.DIR, "workloads", cell + ".json")) as f:
        w = json.load(f)
    for name, limit in w["limits"].items():
        r = w["readings"][name]
        assert r["lower"] < limit < r["upper"], name
        assert limit / r["lower"] > r["upper"] / limit, name


# A second program, added as a file: a one-layer least-squares model
# trained by momentum SGD, with its own parameter tree, inputs, plain
# reference and FLOP count
LSQ_PROGRAM = '''\
"""One linear layer fit by least squares, trained by momentum SGD."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from bench import data

FIRST_STEPS = 3


def mesh_tp(cfg, chips):
    return chips


def _update(cfg, p, m, g):
    m = cfg["momentum"] * m + (1 - cfg["momentum"]) * g
    return p - cfg["lr"] * m, m


class Program:
    def __init__(self, cfg, traffic, mesh):
        d, o, b = cfg["inputs"], cfg["outputs"], traffic["global_batch"]
        self.b1 = cfg["momentum"]
        rep = NamedSharding(mesh, PartitionSpec())
        self.param_sharding = self.opt_sharding = rep
        self.batch_sharding = rep

        def step(params, opt, t, x, y):
            loss, g = jax.value_and_grad(
                lambda p: jnp.mean(jnp.square(x @ p["w"] - y)))(params)
            w, m = _update(cfg, params["w"], opt["m"]["w"], g["w"])
            return {"w": w}, {"m": {"w": m}}, loss

        f32 = jnp.float32
        self.abstract_params = {"w": jax.ShapeDtypeStruct((d, o), f32)}
        self.compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
            self.abstract_params, {"m": self.abstract_params},
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((b, d), f32),
            jax.ShapeDtypeStruct((b, o), f32)).compile()

    def init_opt(self, params):
        return {"m": jax.tree.map(jnp.zeros_like, params)}

    def __call__(self, params, opt_state, step, x, y):
        return self.compiled(params, opt_state, np.int32(step), x, y)


def build(cfg, traffic, mesh):
    return Program(cfg, traffic, mesh)


def init_params(cfg, tp, key, dtype=jnp.float32):
    d, o = cfg["inputs"], cfg["outputs"]
    w = jax.random.normal(jax.random.fold_in(key, 0), (d, o)) * d ** -0.5
    return {"w": w.astype(dtype)}


def batch_pool(cfg, traffic, key):
    d, o, b = cfg["inputs"], cfg["outputs"], traffic["global_batch"]
    kw, kx = jax.random.split(jax.random.fold_in(key, 1))
    w = jax.random.normal(kw, (d, o)) * d ** -0.5
    xs = [jax.random.normal(jax.random.fold_in(kx, i), (b, d))
          for i in range(traffic["pool_batches"])]
    return [(x, x @ w) for x in xs]


def grad_tree(opt_state):
    return opt_state["m"]


def param_tree(params):
    return params


def leaf_norms(cfg, tp, tree):
    return jnp.stack([jnp.linalg.norm(tree["w"].astype(jnp.float32))])


def change_norms(cfg, tp, tree, key):
    init = init_params(cfg, tp, key)
    return leaf_norms(cfg, tp, {"w": tree["w"] - init["w"]})


def first_steps(cfg, tp, seed, batches, *, dtype=jnp.float32,
                precision="highest"):
    key = data.root_key(seed)
    with jax.default_matmul_precision(precision):
        w = init_params(cfg, tp, key, dtype)["w"]
        m = jnp.zeros_like(w)
        losses = []
        for s, (x, y) in enumerate(batches):
            x, y = x.astype(dtype), y.astype(dtype)
            r = x @ w - y
            losses.append(float(jnp.mean(r * r)))
            g = 2 * x.T @ r / r.size
            if s == 0:
                grads = [float(a) for a in leaf_norms(cfg, tp, {"w": g})]
            w, m = _update(cfg, w, m, g)
        change = change_norms(cfg, tp, {"w": w.astype(jnp.float32)}, key)
    return {"losses": losses, "grad_norms": grads,
            "change_norms": [float(a) for a in change]}


def step_flops(cfg, tp, traffic):
    return 4.0 * traffic["global_batch"] * cfg["inputs"] * cfg["outputs"]


def matmul_floor_s(cfg, tp, traffic, peak):
    return step_flops(cfg, tp, traffic) / peak["bf16_flops_per_s"]


def tiny_config(cfg):
    return cfg
'''


def test_additions_are_files_only(tmp_path):
    """A new program, configuration, traffic mix, cell and metric come in
    as files and entries only: the harness runs the new cell's program
    and its reference and compares them, the shipped cells still load
    their own program, and no file the benchmark had changes."""
    from bench import compare, harness

    root = _copy(tmp_path)
    before = _digests(root / "bench")
    b = root / "bench"
    (b / "programs" / "least_squares.py").write_text(LSQ_PROGRAM)
    (b / "configs" / "lsq-256x64.json").write_text(json.dumps(
        {"name": "lsq-256x64", "program": "least_squares", "inputs": 256,
         "outputs": 64, "lr": 8.0, "momentum": 0.9}))
    (b / "traffic" / "rows64.json").write_text(json.dumps(
        {"name": "rows64", "kind": "linear_teacher", "global_batch": 64,
         "pool_batches": 4}))
    (b / "workloads" / "lsq-rows64.json").write_text(json.dumps(
        {"limits": {"loss_gap": 1e-4, "grad_norm_gap": 1e-4,
                    "change_norm_gap": 1e-4}}))
    (b / "metrics" / "host_share.py").write_text(
        'UNIT, LAYER, MOVES = "%", "entry and host loop", "samples_per_s"'
        '\n\n\ndef read(r):\n    return r.host_ms("bench.dispatch")\n')
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "lsq-256x64", "source": "https://example.org/lsq",
        "file": "bench/configs/lsq-256x64.json", "reduced": [],
        "why": "a test"})
    bench["workloads"].append({
        "name": "lsq-rows64", "config": "lsq-256x64", "traffic": "rows64",
        "chips": 1, "why": "a test"})
    bench["per_layer"].append({
        "name": "host_share", "unit": "%", "better": "lower",
        "source": "host_clock", "layer": "entry and host loop",
        "moves": "samples_per_s", "workloads": ["lsq-rows64"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cells = {c.name: c for c in spec.validate(str(root))}
    new = cells["lsq-rows64"]
    assert new.program.__file__ == str(b / "programs" / "least_squares.py")
    assert new.traffic["global_batch"] == 64
    assert "host_share" in {m["name"] for m, _ in new.per_layer}
    assert "host_share" not in {m["name"]
                                for m, _ in cells["dense16k-b256"].per_layer}
    for name in ("dense16k-b256", "phantom16k-p4-b256"):
        assert cells[name].program.__file__ == str(
            b / "programs" / "paper_ffn.py")

    prog, tp, devices = harness.build(new)
    _, _, got = harness.first_steps(new, prog, tp, seed=2 ** 33 + 5)
    ref = harness.reference_steps(new, tp, 2 ** 33 + 5, devices[0])
    nums = compare.numbers(got, ref)
    assert compare.judge(nums, new.limits), nums
    assert ref["losses"][2] < ref["losses"][0]
    assert new.program.step_flops(new.config, tp, new.traffic) > 0

    after = _digests(root / "bench")
    assert {k: after[k] for k in before} == before


def test_config_without_program_is_refused(tmp_path):
    root = _copy(tmp_path)
    p = root / "bench" / "configs" / "paper-ffn-16k-tensor.json"
    cfg = json.loads(p.read_text())
    del cfg["program"]
    p.write_text(json.dumps(cfg))
    with pytest.raises(spec.SpecError, match="names no program"):
        spec.validate(str(root))


def test_metric_disagreeing_with_benchmark_is_refused(tmp_path):
    root = _copy(tmp_path)
    p = root / "bench" / "metrics" / "matmul_ms.py"
    p.write_text(p.read_text().replace('"kernels"', '"matmuls"'))
    with pytest.raises(spec.SpecError, match="matmul_ms"):
        spec.validate(str(root))


def _run(root, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", "dense16k-b256", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)


def test_run_refuses_cpu():
    r = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert not r.stdout.strip()


def test_run_refuses_without_program(tmp_path):
    root = _copy(tmp_path)
    r = _run(str(root), {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "no program" in r.stderr
    assert not r.stdout.strip()
