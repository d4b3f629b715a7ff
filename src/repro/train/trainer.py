"""Training step builder + training loop.

``make_train_step`` assembles the whole step — forward, backward,
spec-aware grad reduction, optional gradient-accumulation microbatching,
grad clipping, optimizer update — as ONE ``shard_map`` over the mesh with
explicit collectives (DESIGN.md §6), jit-compiled with donated state.

The ``Trainer`` adds the production loop around it: data pipeline,
checkpointing (async, elastic), fault tolerance hooks, and telemetry — a
``StepMeter`` wraps every executed step so wall time feeds the
measured-vs-predicted energy ledger (docs/energy_model.md) alongside
loss/throughput logging.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.model import (AUX_LOSS_WEIGHT, forward_train,
                                forward_train_pipeline, model_decls)
from repro.obs import get_metrics, get_tracer, scopes
from repro.parallel.axes import MeshAxes, resolve_spec
from repro.parallel.compat import shard_map
from repro.parallel.grads import reduce_grads
from repro.parallel.params import (ParamDecl, abstract, is_decl,
                                   materialize, specs)
from repro.telemetry import LedgerEntry, StepMeter, analyze_compiled
from repro.train.pipeline import PipelineSchedule  # noqa: F401 (re-export)


def _global_norm(grads, decls, axes: MeshAxes):
    """Spec-aware global grad norm: shard-local sq-sums weighted so every
    element is counted exactly once, psum'd over the full mesh."""
    from repro.parallel.grads import _spec_axes
    total = jnp.float32(0)
    for g, d in zip(jax.tree.leaves(grads),
                    jax.tree.leaves(decls, is_leaf=is_decl)):
        ax = _spec_axes(d.spec)
        repl = 1
        if "dp" not in ax:
            repl *= axes.dp
        if "tp" not in ax:
            repl *= axes.tp
        if "pp" not in ax:
            repl *= axes.pp
        total = total + jnp.sum(jnp.square(g.astype(jnp.float32))) / repl
    return jnp.sqrt(lax.psum(total, axes.all_names))


def make_train_step(cfg: ModelConfig, mesh, optimizer, *,
                    microbatches: int = 1, grad_clip: float = 1.0,
                    batch_spec=None):
    """Returns (jit step_fn(params, opt, step, batch) -> (params, opt,
    metrics), decls, opt_decls).

    On a mesh with a ``pipe`` axis the step runs the 1F1B pipeline:
    ``microbatches`` feeds the wavefront (stage-boundary ppermutes)
    instead of the sequential accumulation scan, layer stacks are
    pipe-sharded per stage, and the spec-aware grad reduction restores
    embed/head gradients across stages via the pipe psum."""
    axes = MeshAxes.from_mesh(mesh)
    decls = model_decls(cfg, axes)
    opt_decls = optimizer.state_decls(decls)
    pipelined = axes.pp > 1

    def loss_fn_pipeline(params, batch):
        # forward_train_pipeline masks loss/valid counts to the last pipe
        # rank and keeps aux stage-local, so each device still
        # differentiates its UNIQUE share of the global objective — the
        # pipe psums below only aggregate for reporting/normalization.
        # Normalization is GLOBAL per-token (sum over all microbatches /
        # global valid count) — the exact microbatches=1 objective.  The
        # accumulation path's mean-of-per-microbatch-means only differs
        # on ragged batches, where per-token weighting is the more
        # faithful objective, so the pipeline keeps it.
        with jax.named_scope(scopes.MODEL):
            sum_loss, n_valid, aux = forward_train_pipeline(
                cfg, axes, params, batch, microbatches)
            red = axes.pp_names + axes.dp_names
            nv_g = lax.psum(n_valid, red).astype(jnp.float32)
            nv_g = jnp.maximum(nv_g, 1.0)
            # aux is a per-microbatch MEAN summed over the M wavefront
            # microbatches — divide by M so the effective aux weight
            # matches the accumulation path (which averages grads over
            # microbatches)
            mb = max(microbatches, 1)
            obj = (sum_loss / nv_g
                   + AUX_LOSS_WEIGHT * aux / (axes.dp * mb)) / axes.tp
            ce_report = lax.psum(sum_loss, red) / nv_g
            return obj, ce_report

    def loss_fn(params, batch):
        with jax.named_scope(scopes.MODEL):
            sum_loss, n_valid, aux = forward_train(cfg, axes, params, batch)
            # Differentiate each device's UNIQUE share of the global
            # objective: psum-ing the scalar pre-grad would inflate grads
            # by the device count (psum's transpose under shard_map is
            # psum).  The xent sum is replicated across tp (every tp rank
            # computes all local tokens), hence the 1/tp; cross-dp sums
            # happen in reduce_grads.
            nv_g = lax.psum(n_valid, axes.dp_names).astype(jnp.float32)
            nv_g = jnp.maximum(nv_g, 1.0)
            obj = (sum_loss / nv_g
                   + AUX_LOSS_WEIGHT * aux / axes.dp) / axes.tp
            ce_report = lax.psum(sum_loss, axes.dp_names) / nv_g
            return obj, ce_report

    def step_fn(params, opt_state, step, batch):
        if pipelined:
            (total, ce), grads = jax.value_and_grad(
                loss_fn_pipeline, has_aux=True)(params, batch)
        elif microbatches == 1:
            (total, ce), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch)
        else:
            from repro.train.pipeline import split_batch_microbatches
            mb_batch = split_batch_microbatches(batch, microbatches)

            def acc_body(carry, mb):
                g_acc, ce_acc = carry
                (_t, ce), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                return (g_acc, ce_acc + ce), None

            g0 = jax.tree.map(lambda d: jnp.zeros(_local_shape(d, axes),
                                                  jnp.float32),
                              decls, is_leaf=is_decl)
            (grads, ce), _ = lax.scan(acc_body, (g0, jnp.float32(0)),
                                      mb_batch)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            ce = ce / microbatches

        grads = reduce_grads(grads, decls, axes)
        with jax.named_scope(scopes.OPTIMIZER):
            gnorm = _global_norm(grads, decls, axes)
            if grad_clip > 0:
                scale = jnp.minimum(1.0,
                                    grad_clip / jnp.maximum(gnorm, 1e-9))
                grads = jax.tree.map(lambda g: g * scale, grads)
            params, opt_state = optimizer.update(grads, opt_state, params,
                                                 step)
        return params, opt_state, {"loss": ce, "grad_norm": gnorm}

    pspecs = jax.tree.map(lambda s: resolve_spec(s, axes), specs(decls))
    ospecs = jax.tree.map(lambda s: resolve_spec(s, axes), specs(opt_decls))
    if batch_spec is None:
        batch_spec = P("dp", None)   # prefix spec: [B, S]-shaped leaves
    bspecs = jax.tree.map(lambda s: resolve_spec(s, axes), batch_spec,
                          is_leaf=lambda x: isinstance(x, P))

    sharded = shard_map(
        step_fn, mesh=mesh,
        in_specs=(pspecs, ospecs, P(), bspecs),
        out_specs=(pspecs, ospecs, P()),
        check_vma=False)
    return (jax.jit(sharded, donate_argnums=(0, 1)), decls, opt_decls)


def _local_shape(d: ParamDecl, axes: MeshAxes):
    shape = list(d.shape)
    for dim, e in enumerate(d.spec):
        if e is None:
            continue
        entries = e if isinstance(e, tuple) else (e,)
        f = 1
        for name in entries:
            f *= axes.tp if name == "tp" else axes.dp
        shape[dim] //= f
    return tuple(shape)


# ---------------------------------------------------------------------------
# production loop
# ---------------------------------------------------------------------------

@dataclass
class TrainState:
    params: object
    opt_state: object
    step: int


class Trainer:
    """Production training loop: data, checkpoints, fault tolerance."""

    def __init__(self, cfg: ModelConfig, mesh, optimizer, dataset, *,
                 microbatches: int = 1, grad_clip: float = 1.0,
                 batch_spec=None, checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 100, keep_checkpoints: int = 3,
                 log_every: int = 10, log_fn: Callable = print,
                 meter: Optional[StepMeter] = None, ledger=None,
                 straggler=None, restart_policy=None, watchdog=None):
        self.cfg, self.mesh, self.optimizer = cfg, mesh, optimizer
        self.dataset = dataset
        self.log_every, self.log_fn = log_every, log_fn
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every
        self.keep_checkpoints = keep_checkpoints
        self.meter = meter or StepMeter(f"train_{cfg.name}", warmup=1)
        self.ledger = ledger
        self.straggler = straggler          # StragglerDetector | None
        self.restart_policy = restart_policy  # RestartPolicy | None
        self.watchdog = watchdog            # EnergyDriftWatchdog | None
        self._ledger_window = 0
        self.step_fn, self.decls, self.opt_decls = make_train_step(
            cfg, mesh, optimizer, microbatches=microbatches,
            grad_clip=grad_clip, batch_spec=batch_spec)
        self._ckpt = None
        if checkpoint_dir:
            from repro.train.checkpoint import CheckpointManager
            self._ckpt = CheckpointManager(
                checkpoint_dir, keep=keep_checkpoints)

    def init_state(self, seed: int = 0) -> TrainState:
        params = materialize(self.decls, seed)
        return TrainState(params, self.optimizer.init(params), 0)

    def restore_or_init(self, seed: int = 0) -> TrainState:
        if self._ckpt is not None:
            restored = self._ckpt.restore_latest(self.decls, self.opt_decls,
                                                 self.mesh)
            if restored is not None:
                self.log_fn(f"[trainer] restored step {restored.step}")
                return restored
        return self.init_state(seed)

    def run(self, state: TrainState, num_steps: int) -> TrainState:
        from repro.train.fault import note_step_time
        params, opt_state = state.params, state.opt_state
        step = state.step
        losses = []
        axes = MeshAxes.from_mesh(self.mesh)
        impl = ("phantom" if self.cfg.uses_phantom_sites() else "dense")
        tracer = get_tracer()
        mx = get_metrics()
        steps_c = mx.counter("train_steps_total",
                             "executed training steps")
        step_h = mx.histogram("train_step_seconds",
                              "metered train step wall seconds")
        loss_g = mx.gauge("train_loss", "last observed training loss")
        run_span = tracer.begin("train/run", cat="train",
                                arch=self.cfg.name, impl=impl,
                                start_step=step, num_steps=num_steps)
        try:
            while step < num_steps:
                batch = self.dataset(step)
                with tracer.span("train/step", cat="train", step=step,
                                 arch=self.cfg.name):
                    if self.watchdog is not None and \
                            self.watchdog.capture_pending():
                        params, opt_state, metrics = self.watchdog.capture(
                            self.meter.call, self.step_fn, params,
                            opt_state, jnp.int32(step), batch)
                    else:
                        params, opt_state, metrics = self.meter.call(
                            self.step_fn, params, opt_state,
                            jnp.int32(step), batch)
                step += 1
                losses.append(metrics)
                dt_s = self.meter.times_us[-1] * 1e-6
                steps_c.inc(suite="trainer")
                step_h.observe(dt_s, suite="trainer")
                loss_g.set(float(metrics["loss"]), suite="trainer")
                if self.watchdog is not None:
                    # step already advanced: name the step that ran
                    self.watchdog.observe(step - 1, dt_s)
                # straggler wiring: a flagged slow step emits a ledger
                # event and may ask for an out-of-cadence checkpoint
                decision = note_step_time(
                    self.straggler, self.restart_policy, step,
                    self.meter.times_us[-1] * 1e-6, self.ledger,
                    name=f"straggler_{self.cfg.name}", arch=self.cfg.name,
                    impl=impl, p=axes.tp)
                if step % self.log_every == 0:
                    m = jax.tree.map(lambda *xs: float(sum(map(float, xs)))
                                     / len(xs), *losses)
                    recent = self.meter.times_us[-self.log_every:]
                    dt_ms = sum(recent) / len(recent) / 1e3
                    self.log_fn(
                        f"[trainer] step {step} loss {m['loss']:.4f} "
                        f"gnorm {m['grad_norm']:.3f} {dt_ms:.0f} ms/it")
                    losses = []
                if self._ckpt is not None and (
                        step % self.checkpoint_every == 0
                        or decision == "checkpoint"):
                    self._ckpt.save_async(step, params, opt_state)
        finally:
            # a crash mid-loop must not abandon a queued async save —
            # errors already in flight take precedence over flush errors
            if self._ckpt is not None:
                self._ckpt.flush(raise_errors=False)
            if self.ledger is not None:
                self.ledger.flush()
        if self._ckpt is not None:
            self._ckpt.flush()
        if self.ledger is not None:
            # link BEFORE end(): the event dict is copied at end time
            run_span.link_ledger(self.record_to(self.ledger))
        tracer.end(run_span.annotate(final_step=step))
        return TrainState(params, opt_state, step)

    # --- telemetry -------------------------------------------------------

    def measure_compiled(self, state: TrainState, batch):
        """Measured per-device costs (flops / HBM / collective wire
        bytes) of the lowered train step, for the energy ledger."""
        axes = MeshAxes.from_mesh(self.mesh)
        compiled = self.step_fn.lower(
            state.params, state.opt_state, jnp.int32(state.step),
            batch).compile()
        return analyze_compiled(compiled, default_group=axes.tp)

    def record_to(self, ledger, predicted=None, name=None,
                  measured_extra=None) -> "LedgerEntry":
        """Flush this trainer's metered steps to a Ledger.  Resets the
        meter so repeated ``run()`` calls record disjoint windows."""
        axes = MeshAxes.from_mesh(self.mesh)
        measured = self.meter.summary()
        if measured_extra:
            measured.update(measured_extra)
        impl = ("phantom" if self.cfg.uses_phantom_sites() else "dense")
        entry = ledger.record(LedgerEntry(
            name=name or f"train_{self.cfg.name}", suite="trainer",
            kind="train", arch=self.cfg.name, impl=impl, p=axes.tp,
            measured=measured, predicted=predicted,
            extra={"window": self._ledger_window, "pp": axes.pp,
                   "dp": axes.dp}))
        self.meter.reset(warm=True)
        self._ledger_window += 1
        return entry


# ---------------------------------------------------------------------------
# pilot runs (the planner's iso-loss measurements)
# ---------------------------------------------------------------------------

@dataclass
class PilotResult:
    """One small training run the planner fits loss curves from."""
    name: str
    strategy: str                  # projection kind at the planned site
    width: int
    tp: int
    k: int
    steps_run: int
    final_loss: float
    losses: list                   # per-step loss trajectory
    target_loss: Optional[float] = None
    iters_to_target: Optional[int] = None   # None = censored (never hit)
    wall_us_median: float = 0.0

    def as_dict(self) -> dict:
        return {"name": self.name, "strategy": self.strategy,
                "width": self.width, "tp": self.tp, "k": self.k,
                "steps_run": self.steps_run, "final_loss": self.final_loss,
                "target_loss": self.target_loss,
                "iters_to_target": self.iters_to_target,
                "wall_us_median": self.wall_us_median}


def pilot_ffn_run(cfg: ModelConfig, mesh, *, steps: int, batch: int,
                  target_loss: Optional[float] = None, lr: float = 3e-3,
                  seed: int = 0, ledger=None,
                  stop_at_target: bool = False) -> PilotResult:
    """Train a small paper-FFN on the Gaussian-teacher dataset and
    record the loss trajectory — the planner's quality measurement.

    Runs ``steps`` iterations, recording the FIRST step at which
    ``target_loss`` is reached (the measured ν the iso-loss frontier
    prices plans with) while continuing to the full budget so the final
    loss is comparable across pilots (``stop_at_target=True`` restores
    the cheap early-exit when only ν is wanted).  Every executed step
    is metered and the run lands in ``ledger`` (suite ``planner``) so
    pilot costs are auditable in the same report as everything else."""
    from repro.core.ffn import ffn_strategy, init_ffn, make_ffn_train_step
    from repro.data.synthetic import TeacherDataset
    from repro.optim import AdamW

    axes = MeshAxes.from_mesh(mesh)
    st = ffn_strategy(cfg, axes.tp)
    opt = AdamW(lr, weight_decay=0.0)
    step_fn, decls, _ = make_ffn_train_step(cfg, mesh, opt, batch)
    params, opt_state = init_ffn(cfg, mesh, opt, seed=seed)
    ds = TeacherDataset(cfg.ffn_width, batch, seed=seed)
    meter = StepMeter(f"pilot_{cfg.name}", warmup=1)

    losses = []
    iters_to_target = None
    pilot_span = get_tracer().begin(
        "plan/pilot", cat="plan", arch=cfg.name, strategy=st.kind,
        width=cfg.ffn_width, tp=axes.tp, k=getattr(st, "k", 0))
    for s in range(steps):
        x, y = ds(s)
        params, opt_state, loss = meter.call(
            step_fn, params, opt_state, jnp.int32(s), x, y)
        losses.append(float(loss))
        if target_loss is not None and iters_to_target is None \
                and losses[-1] <= target_loss:
            iters_to_target = s + 1
            if stop_at_target:
                break
    get_metrics().counter("plan_pilot_steps_total",
                          "training steps spent in planner pilots").inc(
                              len(losses), arch=cfg.name)

    res = PilotResult(
        name=f"pilot_{cfg.name}", strategy=st.kind, width=cfg.ffn_width,
        tp=axes.tp, k=getattr(st, "k", 0), steps_run=len(losses),
        final_loss=losses[-1] if losses else float("nan"), losses=losses,
        target_loss=target_loss, iters_to_target=iters_to_target,
        wall_us_median=meter.median_us())
    if ledger is not None:
        pilot_span.link_ledger(ledger.record(LedgerEntry(
            name=res.name, suite="planner", kind="pilot", arch=cfg.name,
            impl=st.kind, p=axes.tp, measured=dict(
                meter.summary(), final_loss=res.final_loss,
                iterations=iters_to_target or len(losses)),
            extra={"width": res.width, "k": res.k,
                   "target_loss": target_loss,
                   "censored": iters_to_target is None})))
    get_tracer().end(pilot_span.annotate(
        steps_run=res.steps_run, final_loss=res.final_loss,
        iters_to_target=iters_to_target))
    return res
