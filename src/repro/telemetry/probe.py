"""Measured-vs-predicted probe for the paper-FFN step.

``make_ffn_probe_step`` builds a pure fwd+bwd step (loss + grads w.r.t.
params AND inputs, no optimizer) for the strategy ``cfg`` selects, as one
``shard_map`` over the mesh — the same operator schedule as
``core/ffn.make_ffn_train_step`` with two deliberate differences that
make the per-operator account exact:

  * layers are compiled UNROLLED (``core/ffn`` always unrolls the
    paper-FFN stack): XLA's cost analysis counts a scan body once, so
    totals from a scanned compile would be per-layer-scale, not
    per-step;
  * input gradients are requested too: the analytic Table II schedule
    charges every layer an AG fwd + RS bwd, but the first layer's
    backward collective (and its input-grad GEMM) is dead code when the
    input is a constant — differentiating w.r.t. the input keeps the
    schedule complete so measured/predicted ratios pin to ~1.

``measure_ffn_step`` compiles the probe, extracts measured HLO costs,
optionally executes a few metered steps, and returns the (measured,
predicted) pair the ledger joins.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.parallel.axes import MeshAxes, resolve_spec
from repro.parallel.compat import shard_map
from repro.parallel.params import abstract, materialize, specs
from repro.telemetry.compiled import analyze_compiled
from repro.telemetry.meter import StepMeter
from repro.telemetry.predict import ffn_step_prediction


def make_ffn_probe_step(cfg, mesh, global_batch: int):
    """Returns (jit probe_fn(params, x, y) -> (loss, grads), decls)."""
    from repro.core.ffn import ffn_apply, ffn_decls
    axes = MeshAxes.from_mesh(mesh)
    decls = ffn_decls(cfg, axes)
    n = cfg.ffn_width

    def probe(params, x, y):
        def loss_fn(p_, x_):
            out = ffn_apply(cfg, axes, p_, x_)
            return jnp.sum(jnp.square(out - y)) / (global_batch * n)

        loss, (gp, gx) = jax.value_and_grad(loss_fn,
                                            argnums=(0, 1))(params, x)
        # dp grad sync (the train step's reduction) so returned param
        # grads are global — a no-op collective on the dp=1 bench meshes
        if axes.dp > 1:
            gp = jax.tree.map(lambda g: lax.psum(g, axes.dp_names), gp)
        return lax.psum(loss, axes.all_names), (gp, gx)

    pspecs = jax.tree.map(lambda s: resolve_spec(s, axes), specs(decls))
    bspec = resolve_spec(P("dp", "tp"), axes)
    fn = shard_map(probe, mesh=mesh, in_specs=(pspecs, bspec, bspec),
                   out_specs=(P(), (pspecs, bspec)), check_vma=False)
    return jax.jit(fn), decls


def make_ffn_pipeline_probe_step(cfg, mesh, global_batch: int):
    """Pipelined analogue of ``make_ffn_probe_step``: the 1F1B wavefront
    with the tick loop (``scan_layers=False``) and the per-stage layer
    loops (always) unrolled, input grads kept — so the lowered HLO
    contains every wavefront tick's collectives (XLA counts a scanned
    tick body once) and the ppermute count is deterministic."""
    from repro.core.ffn import ffn_decls, make_ffn_stage_fn
    from repro.train.pipeline import pipeline_run, split_microbatches
    cfg = cfg.replace(scan_layers=False)
    axes = MeshAxes.from_mesh(mesh)
    decls = ffn_decls(cfg, axes)
    n = cfg.ffn_width
    M = max(cfg.microbatches, 1)

    def probe(params, x, y):
        def loss_fn(p_, x_):
            x_mb = split_microbatches(x_, M)
            y_mb = split_microbatches(y, M)
            stage_fn = make_ffn_stage_fn(cfg, axes, p_)
            y_hat, _aux = pipeline_run(stage_fn, x_mb, axes, unroll=True)
            sse = jnp.sum(jnp.square(y_hat - y_mb))
            if axes.pp > 1:
                is_last = lax.axis_index(axes.pp_name) == axes.pp - 1
                sse = jnp.where(is_last, sse, jnp.float32(0))
            return sse / (global_batch * n)

        loss, (gp, gx) = jax.value_and_grad(loss_fn,
                                            argnums=(0, 1))(params, x)
        # the train step's reduction: dp grad sync, plus the pipe psum
        # that restores mixed-stage (pipe-replicated) subtree grads —
        # returned grads are the TRUE global gradients (the equivalence
        # suite compares them across meshes)
        red = (axes.dp_names if axes.dp > 1 else ()) \
            + (axes.pp_names if cfg.pipeline.mixed else ())
        if red:
            gp = jax.tree.map(lambda g: lax.psum(g, red), gp)
        return lax.psum(loss, axes.all_names), (gp, gx)

    pspecs = jax.tree.map(lambda s: resolve_spec(s, axes), specs(decls))
    bspec = resolve_spec(P("dp", "tp"), axes)
    fn = shard_map(probe, mesh=mesh, in_specs=(pspecs, bspec, bspec),
                   out_specs=(P(), (pspecs, bspec)), check_vma=False)
    return jax.jit(fn), decls


def measure_ffn_pipeline_step(cfg, mesh, global_batch: int, *,
                              steps: int = 0, seed: int = 0,
                              meter: Optional[StepMeter] = None
                              ) -> Tuple[dict, dict]:
    """Compile + analyze the pipelined FFN probe on a pp mesh; returns
    the ``(measured, predicted)`` ledger join, with the stage-boundary
    (collective-permute) wire bytes split out on BOTH sides so the
    pipeline_smoke suite can pin their ratio."""
    from repro.telemetry.predict import pipeline_ffn_step_prediction
    axes = MeshAxes.from_mesh(mesh)
    fn, decls = make_ffn_pipeline_probe_step(cfg, mesh, global_batch)
    n = cfg.ffn_width
    x_sds = jax.ShapeDtypeStruct((global_batch, n), jnp.float32)
    compiled = fn.lower(abstract(decls), x_sds, x_sds).compile()
    costs = analyze_compiled(compiled, default_group=axes.tp)
    measured = costs.measured_fields()
    measured["boundary_wire_bytes_per_device"] = (
        costs.collectives.get("collective-permute", {}).get("wire_bytes",
                                                            0.0))
    measured["collectives"] = {
        op: {"count": rec["count"], "wire_bytes": rec["wire_bytes"]}
        for op, rec in costs.collectives.items()}

    if steps > 0:
        meter = meter or StepMeter(f"ffn_pipe_probe_{cfg.name}", warmup=1)
        params = materialize(decls, seed)
        key = jax.random.PRNGKey(seed + 1)
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (global_batch, n), jnp.float32)
        y = jax.random.normal(ky, (global_batch, n), jnp.float32)
        for _ in range(steps + meter.warmup):
            meter.call(compiled, params, x, y)
        for k, v in meter.summary().items():
            if k != "name":
                measured[k] = v

    predicted = pipeline_ffn_step_prediction(
        cfg, axes.pp, axes.tp, axes.dp, global_batch, executed=True)
    return measured, predicted


def measure_ffn_step(cfg, mesh, global_batch: int, *, steps: int = 0,
                     seed: int = 0,
                     meter: Optional[StepMeter] = None
                     ) -> Tuple[dict, dict]:
    """Compile + analyze the FFN probe; run ``steps`` metered executions.

    Returns ``(measured, predicted)`` dicts ready for a LedgerEntry:
    measured carries the compiled-HLO flops / HBM / collective wire bytes
    (and wall stats when ``steps > 0``); predicted is
    ``ffn_step_prediction`` summed from the same strategy objects.
    """
    axes = MeshAxes.from_mesh(mesh)
    p = axes.tp
    fn, decls = make_ffn_probe_step(cfg, mesh, global_batch)
    n = cfg.ffn_width
    x_sds = jax.ShapeDtypeStruct((global_batch, n), jnp.float32)
    compiled = fn.lower(abstract(decls), x_sds, x_sds).compile()
    costs = analyze_compiled(compiled, default_group=p)
    measured = costs.measured_fields()
    measured["collectives"] = {
        op: {"count": rec["count"], "wire_bytes": rec["wire_bytes"]}
        for op, rec in costs.collectives.items()}

    if steps > 0:
        meter = meter or StepMeter(f"ffn_probe_{cfg.name}", warmup=1)
        params = materialize(decls, seed)
        key = jax.random.PRNGKey(seed + 1)
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (global_batch, n), jnp.float32)
        y = jax.random.normal(ky, (global_batch, n), jnp.float32)
        for _ in range(steps + meter.warmup):
            meter.call(compiled, params, x, y)
        for k, v in meter.summary().items():
            if k != "name":
                measured[k] = v

    predicted = ffn_step_prediction(cfg, p, global_batch, training=True)
    return measured, predicted
