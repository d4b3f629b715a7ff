"""chip_smoke.py's phases at tiny sizes on the CPU mesh, its refusal to
run off-TPU, and the compile-cache location rule."""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs.paper_ffn import smoke_config
from repro.launch.mesh import make_local_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_phase_tiny(smoke):
    r = smoke.train_phase(smoke_config(), make_local_mesh(1, 1),
                          batch=32, steps=5)
    assert len(r["losses"]) == 5
    assert r["losses"][-1] < r["losses"][0]


def test_reference_loss_catches_a_wrong_layer(smoke):
    """The step-0 check has teeth: dropping the last layer's relu moves
    the loss far past LOSS_RTOL."""
    from repro.configs.base import dense_projection_map
    from repro.core.ffn import init_ffn
    from repro.data.synthetic import TeacherDataset
    from repro.optim import AdamW
    cfg = smoke_config().replace(projections=dense_projection_map())
    params, _ = init_ffn(cfg, make_local_mesh(1, 1), AdamW(1e-3))
    x, y = TeacherDataset(cfg.ffn_width, 32)(0)
    layers = params["layers"]
    good = float(smoke.reference_loss(layers, x, y))
    h = jax.nn.relu(x @ layers["w"][0] + layers["b"][0])
    bad = float(jnp.mean(jnp.square(h @ layers["w"][1] - y)))
    assert abs(bad - good) / good > 10 * smoke.LOSS_RTOL


def test_fused_phase_tiny(smoke):
    smoke.fused_phase(M=16, K=32, N=32, PK=8,
                      dtypes=(jnp.float32, jnp.bfloat16), interpret=True)


def test_flash_phase_tiny_gqa(smoke):
    smoke.flash_phase(B=1, S=256, H=4, KV=2, hd=32,
                      dtypes=(jnp.float32, jnp.bfloat16), interpret=True)


def test_four_chip_phase_tiny(smoke):
    smoke.four_chip_phase(smoke_config(), make_local_mesh(1, 4),
                          make_local_mesh(1, 1), batch=32, steps=3,
                          interpret=True)


def test_check_raises(smoke):
    with pytest.raises(smoke.SmokeFailure, match="boom"):
        smoke.check(False, "boom")


def _run(script, cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_script_refuses_cpu():
    r = _run(SCRIPT, ROOT)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_script_alone_fails(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    r = _run(str(lone), str(tmp_path))
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_compile_cache_dir(monkeypatch):
    from repro.launch import compile_cache
    saved = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere/cache"

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = compile_cache.enable_compile_cache()
        assert first == compile_cache.enable_compile_cache()
        assert first == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)
