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


def test_additions_are_files_only(tmp_path):
    root = _copy(tmp_path)
    before = _digests(root / "bench")
    b = root / "bench"
    cfg = json.loads((b / "configs" / "paper-ffn-16k-tensor.json")
                     .read_text())
    cfg["name"] = "paper-ffn-4k-tensor"
    cfg["ffn_width"] = 4096
    (b / "configs" / "paper-ffn-4k-tensor.json").write_text(json.dumps(cfg))
    (b / "traffic" / "b512.json").write_text(json.dumps(
        {"name": "b512", "kind": "gaussian_teacher", "global_batch": 512,
         "pool_batches": 4}))
    (b / "workloads" / "dense4k-b512.json").write_text(json.dumps(
        {"limits": {"loss_gap": 1e-3, "grad_norm_gap": 1e-2,
                    "change_norm_gap": 1e-2}}))
    (b / "metrics" / "host_share.py").write_text(
        'UNIT, LAYER, MOVES = "%", "entry and host loop", "samples_per_s"'
        '\n\n\ndef read(r):\n    return r.host_ms("bench.dispatch")\n')
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "paper-ffn-4k-tensor", "source": "https://arxiv.org/abs/"
        "2508.00960", "file": "bench/configs/paper-ffn-4k-tensor.json",
        "reduced": [], "why": "a test"})
    bench["workloads"].append({
        "name": "dense4k-b512", "config": "paper-ffn-4k-tensor",
        "traffic": "b512", "chips": 1, "why": "a test"})
    bench["per_layer"].append({
        "name": "host_share", "unit": "%", "better": "lower",
        "source": "host_clock", "layer": "entry and host loop",
        "moves": "samples_per_s", "workloads": ["dense4k-b512"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cells = {c.name: c for c in spec.validate(str(root))}
    new = cells["dense4k-b512"]
    assert new.config["ffn_width"] == 4096
    assert new.traffic["global_batch"] == 512
    assert "host_share" in {m["name"] for m, _ in new.per_layer}
    assert "host_share" not in {m["name"]
                                for m, _ in cells["dense16k-b256"].per_layer}
    after = _digests(root / "bench")
    assert {k: after[k] for k in before} == before


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
