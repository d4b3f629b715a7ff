"""End-to-end paper-FFN pipelines: TP exactness vs single-device dense,
PP trains to a fixed loss, variants produce identical trajectories, and
the energy-model inequalities hold at the paper's operating points."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, PhantomConfig
from repro.core.ffn import (ffn_model_params, init_ffn, make_ffn_forward,
                            make_ffn_train_step)
from repro.data.synthetic import TeacherDataset, gaussian_teacher
from repro.optim import SGD
from repro.parallel.compat import shard_map


def _cfg(impl, n=64, L=2, k=4, variant="fused"):
    return ModelConfig(name=f"t-{impl}-{variant}", family="ffn",
                       num_layers=L, d_model=n,
                       ffn_width=n, ffn_depth=L, ffn_impl=impl, mlp="relu",
                       phantom=PhantomConfig(k=k, variant=variant))


def _build_step(cfg, mesh, batch):
    """Session-cache maker: one compile per (cfg, mesh, batch) — the
    trains-to-loss and identical-trajectory tests share the SGD(0.3)
    step instead of re-jitting it per case."""
    opt = SGD(0.3)
    step_fn, decls, _ = make_ffn_train_step(cfg, mesh, opt, batch)
    return step_fn, decls, opt


def test_tp_matches_single_device_dense(mesh24):
    """The TP pipeline is an exact reparametrization: forward must equal
    the unsharded dense stack bit-for-bit (up to fp32 reduction order)."""
    cfg = _cfg("dense")
    fwd, decls = make_ffn_forward(cfg, mesh24)
    from repro.parallel.params import materialize
    params = materialize(decls, 1)
    x = jax.random.normal(jax.random.key(0), (8, cfg.ffn_width))
    out = fwd(params, x)
    ref = x
    for l in range(cfg.num_layers):
        ref = jax.nn.relu(ref @ params["layers"]["w"][l]
                          + params["layers"]["b"][l])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("impl,variant", [("dense", "fused"),
                                          ("phantom", "fused"),
                                          ("phantom", "faithful"),
                                          ("phantom", "ring")])
def test_pipeline_trains_to_loss(mesh24, compiled_step_cache, impl,
                                 variant):
    cfg = _cfg(impl, variant=variant)
    step_fn, decls, opt = compiled_step_cache.build(_build_step, cfg,
                                                    mesh24, 16)
    params, opt_state = init_ffn(cfg, mesh24, opt)
    ds = TeacherDataset(cfg.ffn_width, 16)
    first = last = None
    for s in range(60):
        x, y = ds(s)
        params, opt_state, loss = step_fn(params, opt_state, jnp.int32(s),
                                          x, y)
        if first is None:
            first = float(loss)
        last = float(loss)
    assert last < 0.7 * first, (impl, variant, first, last)


def test_variants_identical_training(mesh24, compiled_step_cache):
    """faithful / fused / ring are the SAME model: identical losses.
    (Steps come from the session cache — the fused/faithful/ring compiles
    are shared with test_pipeline_trains_to_loss.)"""
    traces = {}
    for variant in ("faithful", "fused", "ring"):
        cfg = _cfg("phantom", variant=variant)
        step_fn, decls, opt = compiled_step_cache.build(_build_step, cfg,
                                                        mesh24, 16)
        params, opt_state = init_ffn(cfg, mesh24, opt)
        ds = TeacherDataset(cfg.ffn_width, 16)
        losses = []
        for s in range(10):
            x, y = ds(s)
            params, opt_state, loss = step_fn(params, opt_state,
                                              jnp.int32(s), x, y)
            losses.append(float(loss))
        traces[variant] = losses
    np.testing.assert_allclose(traces["faithful"], traces["fused"],
                               rtol=1e-4)
    np.testing.assert_allclose(traces["faithful"], traces["ring"],
                               rtol=1e-4)


def test_pp_model_smaller_and_energy_lower():
    """Paper Table I structure: phantom model smaller; per-iteration
    energy lower at the paper's operating points."""
    from repro.core.energy import (energy_per_iteration, phantom_costs,
                                   tp_costs, TPU_PEAK_FLOPS)
    n, L, batch = 16_384, 2, 64
    for p, k in [(8, 16), (16, 6), (32, 4), (64, 2), (128, 2), (256, 4)]:
        pp_params = ffn_model_params(_cfg("phantom", n=n, L=L, k=k), p)
        tp_params = ffn_model_params(_cfg("dense", n=n, L=L), p)
        assert pp_params < tp_params
        a_t, b_t = tp_costs(n, p, L, batch, TPU_PEAK_FLOPS)
        a_p, b_p = phantom_costs(n, p, L, k, batch, TPU_PEAK_FLOPS)
        assert a_p < a_t and b_p < b_t
        assert (energy_per_iteration(a_p, b_p, p)
                < energy_per_iteration(a_t, b_t, p))


def test_compressed_dp_training_converges(mesh24):
    """Beyond-paper: phantom-style gradient compression on the dp axis
    still trains the paper's FFN (error feedback)."""
    from repro.optim.compress import compressed_dp_psum, init_compress_state
    from repro.parallel.axes import MeshAxes, resolve_spec
    from repro.parallel.params import materialize, specs
    from repro.core.ffn import ffn_decls, ffn_apply
    from jax.sharding import PartitionSpec as P

    cfg = _cfg("phantom")
    axes = MeshAxes.from_mesh(mesh24)
    decls = ffn_decls(cfg, axes)
    params = materialize(decls, 0)
    q_state, err_state = init_compress_state(params, rank=2)

    pspecs = jax.tree.map(lambda s: resolve_spec(s, axes), specs(decls))
    qspecs = jax.tree.map(lambda qq: P(*((None,) * qq.ndim)), q_state)
    especs = jax.tree.map(lambda ee: P(*((None,) * ee.ndim)), err_state)
    bspec = resolve_spec(P("dp", "tp"), axes)

    def step(p, q, e, x, y):
        def loss_fn(pp):
            out = ffn_apply(cfg, axes, pp, x)
            return jnp.sum((out - y) ** 2) / (16 * cfg.ffn_width)
        l, g = jax.value_and_grad(loss_fn)(p)
        # NOTE: q/err for tp-sharded params are per-shard (fine: the
        # compression operates shard-locally, reducing over dp only)
        g, q, e = compressed_dp_psum(g, q, e, axes, rank=2)
        p = jax.tree.map(lambda w, gw: w - 0.3 * gw, p, g)
        return p, q, e, jax.lax.psum(l, axes.all_names)

    fn = jax.jit(shard_map(
        step, mesh=mesh24,
        in_specs=(pspecs, qspecs, especs, bspec, bspec),
        out_specs=(pspecs, qspecs, especs, P()), check_vma=False))

    ds = TeacherDataset(cfg.ffn_width, 16)
    first = last = None
    for s in range(60):
        x, y = ds(s)
        params, q_state, err_state, loss = fn(params, q_state, err_state,
                                              x, y)
        if first is None:
            first = float(loss)
        last = float(loss)
    assert last < 0.8 * first, (first, last)


def _scanned_train_step(cfg, mesh, opt, batch):
    """``make_ffn_train_step`` with its layers in a ``lax.scan``: one
    loop body for every layer, the reference the unrolled stack must
    match."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.core.ffn import ffn_decls, ffn_strategy
    from repro.parallel.axes import MeshAxes, resolve_spec
    from repro.parallel.params import specs

    axes = MeshAxes.from_mesh(mesh)
    st = ffn_strategy(cfg, axes.tp)
    decls = ffn_decls(cfg, axes)
    opt_decls = opt.state_decls(decls)

    def step_fn(params, opt_state, step, x, y):
        def loss_fn(p):
            def body(h, layer):
                return jax.nn.relu(st.apply_shard(layer, h, axes)), None
            out, _ = lax.scan(body, x, p["layers"])
            return jnp.sum(jnp.square(out - y)) / (batch * cfg.ffn_width)

        sse_local, grads = jax.value_and_grad(loss_fn)(params)
        loss = lax.psum(sse_local, axes.all_names)
        grads = jax.tree.map(lambda g: lax.psum(g, axes.dp_names), grads)
        params, opt_state = opt.update(grads, opt_state, params, step)
        return params, opt_state, loss

    pspecs = jax.tree.map(lambda s: resolve_spec(s, axes), specs(decls))
    ospecs = jax.tree.map(lambda s: resolve_spec(s, axes), specs(opt_decls))
    bspec = resolve_spec(P("dp", "tp"), axes)
    return jax.jit(shard_map(
        step_fn, mesh=mesh, in_specs=(pspecs, ospecs, P(), bspec, bspec),
        out_specs=(pspecs, ospecs, P()), check_vma=False))


@pytest.mark.parametrize("plan,tp", [("tensor", 1), ("phantom", 4)])
def test_unrolled_layers_match_a_scanned_stack(plan, tp):
    """The unrolled layer stack trains exactly as a scanned one: the bf16
    operands are the same rounding of the same f32 weights, taken in
    another place.  Three AdamW steps, losses and every parameter."""
    from repro.configs.base import dense_projection_map
    from repro.configs.paper_ffn import config
    from repro.launch.mesh import make_local_mesh
    from repro.optim import AdamW

    n, batch = 256, 16
    cfg = config("paper-ffn-16k").replace(d_model=n, ffn_width=n)
    if plan == "tensor":
        cfg = cfg.replace(projections=dense_projection_map())
    mesh = make_local_mesh(1, tp)
    opt = AdamW(0.3 / n, weight_decay=0.0)
    step, _, _ = make_ffn_train_step(cfg, mesh, opt, batch)
    scanned = _scanned_train_step(cfg, mesh, opt, batch)

    params, opt_state = init_ffn(cfg, mesh, opt, seed=3)
    runs = {}
    for name, fn in (("unrolled", step), ("scanned", scanned)):
        p, o = jax.tree.map(jnp.copy, (params, opt_state))
        ds = TeacherDataset(n, batch, seed=5)
        losses = []
        for s in range(3):
            p, o, loss = fn(p, o, jnp.int32(s), *ds(s))
            losses.append(float(loss))
        runs[name] = losses, p
    np.testing.assert_allclose(runs["unrolled"][0], runs["scanned"][0],
                               rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6, atol=0),
        runs["unrolled"][1], runs["scanned"][1])
