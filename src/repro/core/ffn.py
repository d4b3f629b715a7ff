"""The paper's experimental subject: width-n, depth-L fully-connected
networks trained with MSE on the Gaussian-teacher dataset (§VI), in both
parallelization styles:

  * TP  — conventional tensor parallelism (baseline, paper Fig. 1a)
  * PP  — phantom parallelism (paper Fig. 1b/3/4)

Both run as a single ``shard_map`` over the whole mesh with explicit
collectives, so measured/lowered communication is exactly the paper's
Table II schedule:

  TP per layer:  All-Gather(n/p * batch) fwd, Reduce-Scatter bwd
  PP per layer:  All-Gather(k * batch)   fwd, Reduce-Scatter bwd

This module is used by the paper-reproduction benchmarks (Fig. 5/6/7,
Table I), the examples, and the equivalence tests.

Pipeline parallelism (``cfg.pipeline.stages > 1``): the layer stack is
cut into contiguous stages, each running its OWN per-stage
``ProjectionStrategy`` (tensor or phantom — ``PipelineConfig.
stage_specs``), and the train step executes the 1F1B wavefront of
``train/pipeline.py`` over the ``pipe`` mesh axis, ppermuting the
feature-sharded ``[B_mb, n/tp]`` activation across stage boundaries.  On
a pp=1 mesh the same config runs the stages sequentially — the
equivalence reference.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, PHANTOM_KINDS
from repro.obs import scopes
from repro.parallel.axes import MeshAxes, resolve_spec
from repro.parallel.params import (abstract, is_decl, materialize, specs,
                                   stack)
from repro.parallel.compat import shard_map
from repro.parallel.strategies import make_strategy, site_strategy
from repro.train.pipeline import (PipelineSchedule, pipeline_run,
                                  split_microbatches)


# ---------------------------------------------------------------------------
# declarations (via the ProjectionStrategy API, site "ffn_layer")
# ---------------------------------------------------------------------------

def ffn_strategy(cfg: ModelConfig, tp: int):
    """The one square n x n projection strategy each paper-FFN layer uses."""
    n = cfg.ffn_width
    return site_strategy(cfg, "ffn_layer", n, n, tp, bias=True)


def ffn_stage_strategies(cfg: ModelConfig, tp: int):
    """One strategy per pipeline stage (len == pipeline.stages; a single
    entry for non-pipelined configs).  Per-stage phantom specs fall back
    to the dense site default under the same divisibility guard as
    ``site_strategy``."""
    S = cfg.pipeline.stages
    if S == 1:
        return [ffn_strategy(cfg, tp)]
    n = cfg.ffn_width
    out = []
    for s in range(S):
        spec = cfg.stage_projection_spec(s)
        if spec.kind in PHANTOM_KINDS and n % tp:
            spec = dataclasses.replace(spec, kind="tensor_col")
        out.append(make_strategy(spec, n, n, tp, bias=True))
    return out


def _stack_stages(layer_decls, L_loc: int, S: int):
    """[S, L_loc, ...] stage-stacked decls, stage axis sharded over pp."""
    st = stack(stack(layer_decls, L_loc), S)
    return jax.tree.map(
        lambda d: dataclasses.replace(
            d, spec=P(*(("pp",) + tuple(d.spec)[1:]))),
        st, is_leaf=is_decl)


def ffn_decls(cfg: ModelConfig, axes: MeshAxes):
    L, S = cfg.num_layers, cfg.pipeline.stages
    if S == 1:
        layer = ffn_strategy(cfg, axes.tp).decls()
        return {"layers": stack(layer, L)}
    if L % S:
        raise ValueError(f"{L} layers do not divide into {S} stages")
    sts = ffn_stage_strategies(cfg, axes.tp)
    L_loc = L // S
    if not cfg.pipeline.mixed:
        # homogeneous stages: ONE [S, L_loc, ...] stack, stage axis
        # sharded over the pipe mesh axis — each pipe rank holds exactly
        # its own stage's layers
        return {"stages": _stack_stages(sts[0].decls(), L_loc, S)}
    # mixed per-stage strategies have different param structures, so each
    # stage keeps its own subtree, replicated over the pipe axis (only
    # rank s computes with / gets gradients for stage s; the pipe-psum in
    # the step restores the full gradient everywhere)
    return {f"stage{s}": stack(sts[s].decls(), L_loc)
            for s in range(S)}


def ffn_model_params(cfg: ModelConfig, p: int) -> int:
    """Model size (paper Table I): TP size is p-independent; phantom
    shrinks.  Pipelined configs sum their per-stage strategies."""
    S = cfg.pipeline.stages
    if S == 1:
        return cfg.num_layers * ffn_strategy(cfg, p).param_count()
    L_loc = cfg.num_layers // S
    return sum(L_loc * st.param_count()
               for st in ffn_stage_strategies(cfg, p))


# ---------------------------------------------------------------------------
# forward (inside shard_map; x is the local [B_loc, n/p] feature shard)
# ---------------------------------------------------------------------------

def _act(name: str):
    return {"relu": jax.nn.relu, "gelu": jax.nn.gelu}.get(name, jax.nn.relu)


def ffn_apply(cfg: ModelConfig, axes: MeshAxes, params, x):
    if cfg.pipeline.stages > 1:
        raise ValueError("pipelined FFN configs run through "
                         "make_ffn_train_step / make_ffn_pipeline_probe; "
                         "ffn_apply is the single-stage path")
    return _apply_stack(cfg, axes, ffn_strategy(cfg, axes.tp),
                        params["layers"], x)


def _apply_stack(cfg, axes, st, stack_params, x):
    """Apply an [L, ...] layer stack to a feature shard, one layer at a
    time by static index.

    Paper-FFN stacks are short (L in {2, 6}), so they always run
    unrolled, whatever ``cfg.scan_layers`` says: XLA then casts each
    layer's weights to bf16 inside its matmuls, hands the optimizer each
    layer's gradient where its matmul left it, and drops the first
    layer's unused input gradient.  A ``lax.scan`` would cast the whole
    stack up front, zero-fill an [L, ...] gradient buffer, and compute
    that input gradient in its one backward body."""
    act = _act(cfg.mlp)
    depth = jax.tree.leaves(stack_params)[0].shape[0]
    for i in range(depth):
        layer = jax.tree.map(lambda a: a[i], stack_params)
        x = act(st.apply_shard(layer, x, axes))
    return x


def make_ffn_stage_fn(cfg: ModelConfig, axes: MeshAxes, params):
    """The per-rank ``stage_fn`` for ``pipeline_run`` (call INSIDE
    shard_map).  On a pp>1 mesh each rank applies its own stage — the
    local slice of the pipe-sharded stack, or a ``lax.switch`` over the
    per-stage subtrees when stages mix strategies.  On pp=1 all stages
    run sequentially (the equivalence reference)."""
    S = cfg.pipeline.stages
    sts = ffn_stage_strategies(cfg, axes.tp)
    mixed = cfg.pipeline.mixed

    if axes.pp == 1:
        def stage_fn(x):
            for s in range(S):
                sp = (params[f"stage{s}"] if mixed
                      else jax.tree.map(lambda a: a[s], params["stages"]))
                x = _apply_stack(cfg, axes, sts[s], sp, x)
            return x, jnp.float32(0)
        return stage_fn

    if axes.pp != S:
        raise ValueError(f"mesh pipe axis {axes.pp} != pipeline stages {S}")
    if not mixed:
        local = jax.tree.map(lambda a: a[0], params["stages"])

        def stage_fn(x):
            return (_apply_stack(cfg, axes, sts[0], local, x),
                    jnp.float32(0))
        return stage_fn

    s_idx = lax.axis_index(axes.pp_name)
    branches = [
        (lambda x, s=s: _apply_stack(cfg, axes, sts[s],
                                     params[f"stage{s}"], x))
        for s in range(S)]

    def stage_fn(x):
        return lax.switch(s_idx, branches, x), jnp.float32(0)
    return stage_fn


# ---------------------------------------------------------------------------
# train step (whole step inside one shard_map)
# ---------------------------------------------------------------------------

def make_ffn_train_step(cfg: ModelConfig, mesh, optimizer,
                        global_batch: int):
    """Returns (step_fn, decls, opt_decls).

    step_fn(params, opt_state, step, x, y) -> (params, opt_state, loss)
    jit-compiled; params/opt sharded per decls; x,y sharded (dp, tp).

    Pipelined configs (``cfg.pipeline.stages > 1``) route to the 1F1B
    wavefront step; a pp>1 mesh with a single-stage config is an error.
    """
    axes = MeshAxes.from_mesh(mesh)
    if cfg.pipeline.stages > 1 or axes.pp > 1:
        return _make_ffn_pipeline_train_step(cfg, mesh, optimizer,
                                             global_batch)
    decls = ffn_decls(cfg, axes)
    opt_decls = optimizer.state_decls(decls)
    n = cfg.ffn_width

    def step_fn(params, opt_state, step, x, y):
        def loss_fn(p):
            with jax.named_scope(scopes.MODEL):
                out = ffn_apply(cfg, axes, p, x)
                # local share only — outputs are fully sharded (batch over
                # dp, features over tp) so the local sse IS this device's
                # unique contribution; cross-device sums happen via grad
                # psums.
                return jnp.sum(jnp.square(out - y)) / (global_batch * n)

        sse_local, grads = jax.value_and_grad(loss_fn)(params)
        loss = lax.psum(sse_local, axes.all_names)
        grads = jax.tree.map(lambda g: lax.psum(g, axes.dp_names), grads)
        with jax.named_scope(scopes.OPTIMIZER):
            params, opt_state = optimizer.update(grads, opt_state, params,
                                                 step)
        return params, opt_state, loss

    pspecs = jax.tree.map(lambda s: resolve_spec(s, axes), specs(decls))
    ospecs = jax.tree.map(lambda s: resolve_spec(s, axes), specs(opt_decls))
    bspec = resolve_spec(P("dp", "tp"), axes)

    sharded = shard_map(
        step_fn, mesh=mesh,
        in_specs=(pspecs, ospecs, P(), bspec, bspec),
        out_specs=(pspecs, ospecs, P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0, 1)), decls, opt_decls


def _make_ffn_pipeline_train_step(cfg: ModelConfig, mesh, optimizer,
                                  global_batch: int):
    """1F1B pipelined train step (same signature/contract as
    ``make_ffn_train_step``).

    Microbatching here is the PIPELINE's microbatching: the existing
    ``cfg.microbatches`` splitter feeds the wavefront (M microbatches
    over ``pp`` stages) instead of a sequential accumulation scan.  The
    loss masks to the last pipe rank — every other rank's parameters
    reach the objective only through the ppermute chain, whose transpose
    is the backward pipeline.
    """
    axes = MeshAxes.from_mesh(mesh)
    S = cfg.pipeline.stages
    if axes.pp > 1 and S != axes.pp:
        raise ValueError(f"mesh pipe axis {axes.pp} != pipeline "
                         f"stages {S}")
    decls = ffn_decls(cfg, axes)
    opt_decls = optimizer.state_decls(decls)
    n = cfg.ffn_width
    M = max(cfg.microbatches, 1)
    mixed = cfg.pipeline.mixed

    def step_fn(params, opt_state, step, x, y):
        x_mb = split_microbatches(x, M)
        y_mb = split_microbatches(y, M)

        def loss_fn(p):
            with jax.named_scope(scopes.MODEL):
                stage_fn = make_ffn_stage_fn(cfg, axes, p)
                y_hat, _aux = pipeline_run(stage_fn, x_mb, axes,
                                           unroll=not cfg.scan_layers)
                sse = jnp.sum(jnp.square(y_hat - y_mb))
                if axes.pp > 1:
                    is_last = lax.axis_index(axes.pp_name) == axes.pp - 1
                    sse = jnp.where(is_last, sse, jnp.float32(0))
                return sse / (global_batch * n)

        sse_local, grads = jax.value_and_grad(loss_fn)(params)
        loss = lax.psum(sse_local, axes.all_names)
        # homogeneous stage stacks are pipe-SHARDED (each rank owns its
        # stage's grads); mixed per-stage subtrees are pipe-replicated
        # and need the pipe psum to restore the full gradient everywhere
        red = axes.dp_names + (axes.pp_names if mixed else ())
        if red:
            grads = jax.tree.map(lambda g: lax.psum(g, red), grads)
        with jax.named_scope(scopes.OPTIMIZER):
            params, opt_state = optimizer.update(grads, opt_state, params,
                                                 step)
        return params, opt_state, loss

    pspecs = jax.tree.map(lambda s: resolve_spec(s, axes), specs(decls))
    ospecs = jax.tree.map(lambda s: resolve_spec(s, axes), specs(opt_decls))
    bspec = resolve_spec(P("dp", "tp"), axes)

    sharded = shard_map(
        step_fn, mesh=mesh,
        in_specs=(pspecs, ospecs, P(), bspec, bspec),
        out_specs=(pspecs, ospecs, P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0, 1)), decls, opt_decls


def make_ffn_forward(cfg: ModelConfig, mesh):
    """jit'd forward pass for inference benchmarks."""
    axes = MeshAxes.from_mesh(mesh)
    decls = ffn_decls(cfg, axes)
    pspecs = jax.tree.map(lambda s: resolve_spec(s, axes), specs(decls))
    bspec = resolve_spec(P("dp", "tp"), axes)
    fwd = shard_map(
        partial(ffn_apply, cfg, axes), mesh=mesh,
        in_specs=(pspecs, bspec), out_specs=bspec, check_vma=False)
    return jax.jit(fwd), decls


def init_ffn(cfg: ModelConfig, mesh, optimizer, seed: int = 0):
    """Materialized params + optimizer state (for real training runs)."""
    axes = MeshAxes.from_mesh(mesh)
    decls = ffn_decls(cfg, axes)
    params = materialize(decls, seed)
    opt_state = optimizer.init(params)
    return params, opt_state


def abstract_ffn(cfg: ModelConfig, mesh, optimizer):
    """ShapeDtypeStruct stand-ins for the dry-run path."""
    axes = MeshAxes.from_mesh(mesh)
    decls = ffn_decls(cfg, axes)
    return abstract(decls), abstract(optimizer.state_decls(decls))
