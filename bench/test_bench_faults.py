"""A whole run, at a tiny size on the CPU, with the timed path broken
underneath: ``correct`` has to come out false for each fault a training
cell can have, and true for the sound program."""
import jax
import jax.numpy as jnp
import pytest

import repro.core.ffn as ffn
from bench import harness
from bench.conftest import tiny

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REAL_STEP = ffn.make_ffn_train_step


def _run(cell, seed=3):
    return harness.run(cell, seed, 0.2, False, peak=PEAK, t_process=0.0)


def unchanged_state(cfg, mesh, opt, batch):
    step, d, od = REAL_STEP(cfg, mesh, opt, batch)

    def broken(p, o, s, x, y):
        _, _, loss = step(p, o, s, x, y)
        return p, o, loss
    return jax.jit(broken), d, od


def half_batch(cfg, mesh, opt, batch):
    step, d, od = REAL_STEP(cfg, mesh, opt, batch // 2)

    def broken(p, o, s, x, y):
        return step(p, o, s, x[:batch // 2], y[:batch // 2])
    return jax.jit(broken, donate_argnums=(0, 1)), d, od


def altered_loss(cfg, mesh, opt, batch):
    step, d, od = REAL_STEP(cfg, mesh, opt, batch)

    def broken(p, o, s, x, y):
        p, o, loss = step(p, o, s, x, y)
        return p, o, loss * 1.01
    return jax.jit(broken, donate_argnums=(0, 1)), d, od


def gather_nothing(x, axis_name, *, axis=0, tiled=False, **kw):
    """An all-gather that exchanges nothing: every rank's own block
    stands in for everyone's."""
    n = jax.lax.axis_size(axis_name)
    if tiled:
        return jnp.concatenate([x] * n, axis=axis)
    return jnp.stack([x] * n, axis=axis)


@pytest.mark.parametrize("name,chips", [("dense16k-b256", 1),
                                        ("phantom16k-p4-b256", 4)])
def test_sound_run_is_correct(name, chips):
    r = _run(tiny(name, chips))
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "compared"
    assert set(r["metrics"]) == {"samples_per_s", "step_ms_p95", "mfu",
                                 "setup_s"}


@pytest.mark.parametrize("fault", [unchanged_state, half_batch,
                                   altered_loss])
@pytest.mark.parametrize("name,chips", [("dense16k-b256", 1),
                                        ("phantom16k-p4-b256", 4)])
def test_fault_is_not_correct(monkeypatch, fault, name, chips):
    monkeypatch.setattr(ffn, "make_ffn_train_step", fault)
    r = _run(tiny(name, chips))
    assert not r["correct"], r["compared"]


@pytest.mark.parametrize("name", ["phantom16k-p4-b256", "dense16k-b256"])
def test_exchange_left_out_is_not_correct(monkeypatch, name):
    """The exchange between four chips left out (the tensor
    configuration on four ranks is the tensor p=4 plan)."""
    monkeypatch.setattr(jax.lax, "all_gather", gather_nothing)
    r = _run(tiny(name, 4))
    assert not r["correct"], r["compared"]
