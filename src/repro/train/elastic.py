"""Elastic fault-tolerant training with energy-aware re-planning.

The failure loop the paper's deployment story needs (ROADMAP: the last
seed-stub subsystem), on the paper-FFN subject:

1. A ``SimulatedCluster`` of N hosts heartbeats on a virtual clock while
   the metered step loop trains toward a target loss, checkpointing
   asynchronously on a step cadence (``CheckpointManager`` — atomic
   commits, ``latest``-is-always-complete).
2. On detected device loss the runner flushes pending saves, asks the
   ``RestartPolicy`` for a decision, and RE-SOLVES dp×tp×pp×k for the
   surviving device count with the calibrated energy planner
   (``enumerate_plans`` → HBM filter → ``score_plans`` → sort by total
   energy; tensor pins to the full surviving budget, phantom may
   downsize further).  The winning plan must
   pass the PR-6 static sharding/energy audit before anything executes
   — an un-priceable mesh is rejected and the next-cheapest tried.
3. Training resumes from the latest complete checkpoint on the new
   mesh.  Checkpoints hold GLOBAL host arrays, so a same-model-class
   re-plan (dense→dense on any mesh; phantom→phantom at the same
   (k, tp)) restores EXACTLY — flat [L, ...] stacks and pipelined
   [S, L/S, ...] stage stacks are pure reshapes of each other.  A
   model-CLASS change — the paper-sanctioned downsize from tensor onto
   a phantom plan with fewer devices — reconstructs each layer's dense
   equivalent and re-factors it through the truncated-SVD phantom
   initializer (``core/lowrank.svd_phantom_init``, the lowrank-distill
   path); optimizer moments cannot survive a class change and restart
   at zero (a priced recovery cost: the replayed-step count covers the
   re-warming iterations).
4. Every recovery is priced first-class: ``telemetry.recovery_account``
   joins the calibrated per-iteration step energy (useful vs replayed)
   with checkpoint IO and restart time (restore + re-plan + compile,
   charged at static power B across the waiting devices), and the run
   lands in the ledger (kind ``elastic``) with the account in its
   ``extra`` — the BENCH_report.json columns the elastic smoke suite
   and CI band-check.

``python -m repro.launch.train --elastic --kill-at-step N`` drives this
loop from the CLI; ``benchmarks/elastic_smoke.py`` asserts the
replay-overhead ratio band end-to-end.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import PHANTOM_KINDS
from repro.obs import get_metrics, get_tracer
from repro.parallel.axes import MeshAxes, resolve_spec
from repro.planner.space import PlanCandidate
from repro.telemetry import LedgerEntry, StepMeter, recovery_account
from repro.train.checkpoint import CheckpointManager
from repro.train.fault import (FaultScript, RestartPolicy,
                               SimulatedCluster, StragglerDetector,
                               note_step_time)


# ---------------------------------------------------------------------------
# configuration & results
# ---------------------------------------------------------------------------

@dataclass
class ElasticConfig:
    """One elastic training run (paper-FFN teacher-matching subject)."""
    workdir: str                    # checkpoint + heartbeat directories
    devices: int = 8                # full-fleet device budget
    hosts: int = 4                  # simulated hosts (devices % hosts == 0)
    width: int = 256                # FFN width n (fixed across re-plans)
    depth: int = 2                  # layers L
    batch: int = 64                 # global rows per step
    target_loss: float = 0.05
    max_steps: int = 300
    checkpoint_every: int = 10
    keep_checkpoints: int = 3
    strategies: Tuple[str, ...] = ("tensor_col", "phantom")
    initial_strategy: Optional[str] = None   # pin phase-0 family
    ks: Tuple[int, ...] = (4, 8, 16)
    pps: Tuple[int, ...] = (1,)
    hbm_gb: float = 16.0
    lr: float = 3e-3
    seed: int = 0
    max_restarts: int = 4
    heartbeat_timeout_s: float = 2.5   # virtual seconds
    virtual_dt: float = 1.0            # virtual seconds per step
    audit_replan: bool = True          # PR-6 static audit gate
    straggler_window: int = 50
    straggler_threshold: float = 4.0
    # watchdog fixtures: sleep inside the metered call at these steps so
    # the step runs ~slow_factor x its healthy wall time (the injected
    # anomaly the energy-drift watchdog must trip on)
    slow_steps: Tuple[int, ...] = ()
    slow_factor: float = 6.0
    # report each step to the watchdog, straggler detector and step
    # histogram as virtual_dt seconds (a slow step as slow_factor x
    # that, with no sleep) instead of its wall time: the same run then
    # feeds them the same numbers on any machine
    virtual_step_time: bool = False


@dataclass
class ElasticResult:
    reached_target: bool
    aborted: bool
    final_loss: float
    final_step: int
    phases: List[dict]
    recoveries: List[dict]
    account: dict
    plan_names: List[str] = field(default_factory=list)
    losses: List[float] = field(default_factory=list)

    def as_dict(self) -> dict:
        return {"reached_target": self.reached_target,
                "aborted": self.aborted, "final_loss": self.final_loss,
                "final_step": self.final_step, "phases": self.phases,
                "recoveries": self.recoveries, "account": self.account,
                "plan_names": self.plan_names}


# ---------------------------------------------------------------------------
# energy-aware re-planning (with the PR-6 static audit gate)
# ---------------------------------------------------------------------------

def plan_from_dict(d: dict) -> PlanCandidate:
    """Rebuild the checkpoint-meta plan record (``PlanCandidate.
    as_dict``) — restore needs the class it is converting FROM."""
    return PlanCandidate(
        dp=int(d["dp"]), tp=int(d["tp"]), strategy=d["strategy"],
        width=int(d["width"]), depth=int(d["depth"]),
        batch=int(d["batch"]), k=int(d.get("k", 0)),
        pp=int(d.get("pp", 1)), site=d.get("site", "ffn_layer"),
        microbatches=int(d.get("microbatches", 1)))


def solve_plan(device_budget: int, cfg: ElasticConfig, calib, *,
               strategies: Optional[Sequence[str]] = None,
               audit: Optional[bool] = None,
               mesh_cache: Optional[dict] = None):
    """Re-solve dp×tp×pp×k for ``device_budget`` devices.

    The enumeration keeps the planner's family semantics: tensor plans
    pin to the FULL surviving budget (idling paid-for devices under the
    baseline would make every comparison trivially winnable), while
    phantom plans may downsize further — the paper-sanctioned "fewer
    devices at the same loss" option.  Candidates are filtered for HBM
    fit, priced with the calibrated model, and the energy-sorted list
    is walked until one passes the static audit (skipped when ``audit``
    is off).  Returns ``(ScoredPlan, audit_results)``; raises
    RuntimeError when no plan survives."""
    from repro.planner import (Constraints, enumerate_plans,
                               filter_feasible, score_plans)
    audit = cfg.audit_replan if audit is None else audit
    candidates = enumerate_plans(
        device_budget, width=cfg.width, depth=cfg.depth, batch=cfg.batch,
        strategies=tuple(strategies or cfg.strategies), ks=cfg.ks,
        pps=cfg.pps)
    feasible, _rej = filter_feasible(candidates, Constraints(
        max_devices=device_budget,
        hbm_bytes_per_device=cfg.hbm_gb * 2 ** 30))
    if not feasible:
        raise RuntimeError(
            f"no feasible plan for {device_budget} device(s) "
            f"(width={cfg.width}, strategies={cfg.strategies})")
    scored = score_plans(feasible, calib, iterations=float(cfg.max_steps))
    scored.sort(key=lambda s: (s.energy_j_total, s.plan.name))
    audit_results: Dict[str, dict] = {}
    if not audit:
        return scored[0], audit_results
    from repro.analysis import audit_plans
    from repro.launch.mesh import make_local_mesh
    mesh_cache = mesh_cache if mesh_cache is not None else {}
    for s in scored:
        key = (s.plan.dp, s.plan.tp, s.plan.pp)
        if key not in mesh_cache:
            mesh_cache[key] = make_local_mesh(*key)
        res = audit_plans([s.plan], mesh_cache=mesh_cache)
        audit_results.update(res)
        if res[s.plan.name]["ok"]:
            s.notes["audit_ok"] = True
            return s, audit_results
    raise RuntimeError(
        f"static audit rejected every plan for {device_budget} "
        f"device(s): { {k: v['errors'][:1] for k, v in audit_results.items()} }")


# ---------------------------------------------------------------------------
# cross-mesh / cross-class parameter conversion
# ---------------------------------------------------------------------------

def _plan_class(plan: PlanCandidate) -> tuple:
    """The model class a plan trains: the phantom family is (k, tp)-
    dependent (paper Table I), the dense family is mesh-independent."""
    if plan.strategy in PHANTOM_KINDS:
        return ("phantom", plan.k, plan.tp)
    return ("dense",)


def _nest(flat: Dict[str, np.ndarray]) -> dict:
    root: dict = {}
    for key, arr in flat.items():
        parts = key.split("/")
        d = root
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = arr
    return root


def _to_flat_layers(plan: PlanCandidate, tree: dict) -> Dict[str, np.ndarray]:
    """Collapse a host param tree to flat [L, ...] leaf stacks: the
    pipelined layout {"stages": [S, L/S, ...]} is a reshape of the flat
    {"layers": [L, ...]} layout (homogeneous stages; global arrays)."""
    if plan.pp > 1:
        st = tree["stages"]
        return {k: np.asarray(v).reshape((plan.depth,) + v.shape[2:])
                for k, v in st.items()}
    return {k: np.asarray(v) for k, v in tree["layers"].items()}


def _from_flat_layers(plan: PlanCandidate,
                      flat: Dict[str, np.ndarray]) -> dict:
    if plan.pp > 1:
        S, L_loc = plan.pp, plan.depth // plan.pp
        return {"stages": {k: v.reshape((S, L_loc) + v.shape[1:])
                           for k, v in flat.items()}}
    return {"layers": dict(flat)}


def convert_ffn_params(plan_old: PlanCandidate, plan_new: PlanCandidate,
                       host_params: dict, host_opt: Optional[dict] = None):
    """Convert a GLOBAL host param tree between plans.

    Same model class → exact (reshape only; dense is mesh-independent,
    phantom at fixed (k, tp) likewise — dp/pp only re-shard).  Class
    change → per-layer dense reconstruction, then either direct use
    (→ tensor) or truncated-SVD re-factoring (→ phantom, the
    paper-sanctioned lowrank-distill downsize).  Returns ``(params,
    opt_or_None, distilled)``; the optimizer tree only survives the
    exact path (same reshape on every moment leaf)."""
    if plan_old.width != plan_new.width or plan_old.depth != plan_new.depth:
        raise ValueError("elastic re-plans keep the task fixed: width/"
                         f"depth changed {plan_old.name}->{plan_new.name}")
    flat_p = _to_flat_layers(plan_old, host_params)
    if _plan_class(plan_old) == _plan_class(plan_new):
        new_p = _from_flat_layers(plan_new, flat_p)
        new_opt = None
        if host_opt is not None:
            new_opt = {moment: _from_flat_layers(
                plan_new, _to_flat_layers(plan_old, sub))
                for moment, sub in host_opt.items()}
        return new_p, new_opt, False
    L = plan_old.depth
    n = plan_old.width
    dense: List[Tuple[np.ndarray, np.ndarray]] = []
    if plan_old.strategy in PHANTOM_KINDS:
        from repro.core.phantom import phantom_dense_equivalent
        for layer in range(L):
            W = np.asarray(phantom_dense_equivalent(
                {k: flat_p[k][layer] for k in ("L", "C", "D")}))
            b = (np.asarray(flat_p["b"][layer]) if "b" in flat_p
                 else np.zeros(n, np.float32))
            dense.append((W, b))
    else:
        for layer in range(L):
            b = (np.asarray(flat_p["b"][layer]) if "b" in flat_p
                 else np.zeros(n, np.float32))
            dense.append((np.asarray(flat_p["w"][layer]), b))
    if plan_new.strategy in PHANTOM_KINDS:
        from repro.core.lowrank import svd_phantom_init
        cols = {k: [] for k in ("L", "C", "D")}
        bs = []
        for W, b in dense:
            fac = svd_phantom_init(W, plan_new.tp, plan_new.k)
            for k in cols:
                cols[k].append(np.asarray(fac[k], np.float32))
            bs.append(np.asarray(b, np.float32))
        flat_new = {k: np.stack(v) for k, v in cols.items()}
        flat_new["b"] = np.stack(bs)
    else:
        flat_new = {
            "w": np.stack([W for W, _ in dense]).astype(np.float32),
            "b": np.stack([b for _, b in dense]).astype(np.float32)}
    return _from_flat_layers(plan_new, flat_new), None, True


def place_host_tree(host_tree: dict, decls, mesh):
    """device_put a GLOBAL host tree onto ``mesh`` with each decl's
    NamedSharding (the elastic restore's final hop)."""
    from jax.sharding import NamedSharding
    axes = MeshAxes.from_mesh(mesh)

    def place(decl, arr):
        spec = resolve_spec(decl.spec, axes)
        return jax.device_put(np.asarray(arr), NamedSharding(mesh, spec))

    from repro.parallel.params import is_decl
    return jax.tree.map(place, decls, host_tree, is_leaf=is_decl)


# ---------------------------------------------------------------------------
# the failure loop
# ---------------------------------------------------------------------------

class _Phase:
    """Bookkeeping for one plan/mesh the run executed on."""

    def __init__(self, scored, start_step: int, replayed: int,
                 compile_s: float, restart: bool):
        self.scored = scored
        self.plan = scored.plan
        self.start_step = start_step
        self.steps = 0
        self.replayed = replayed
        self.compile_s = compile_s
        self.restart = restart
        self.t0 = time.perf_counter()
        self.io0 = (0.0, 0)   # (io_seconds, io_bytes) at phase start
        self.ckpt_io_s = 0.0
        self.ckpt_io_bytes = 0.0
        self.wall_s = 0.0

    def close(self, mgr: CheckpointManager):
        self.ckpt_io_s = mgr.io_seconds - self.io0[0]
        self.ckpt_io_bytes = mgr.io_bytes - self.io0[1]
        self.wall_s = time.perf_counter() - self.t0

    def as_dict(self) -> dict:
        return {"plan": self.plan.name, "strategy": self.plan.strategy,
                "mesh": [self.plan.dp, self.plan.tp, self.plan.pp],
                "k": self.plan.k, "devices": self.plan.devices,
                "start_step": self.start_step, "steps": self.steps,
                "replayed_steps": self.replayed,
                "energy_j_per_iter": self.scored.energy_j_per_iter,
                "compile_s": self.compile_s, "restart": self.restart,
                "ckpt_io_s": self.ckpt_io_s,
                "ckpt_io_bytes": self.ckpt_io_bytes,
                "wall_s": self.wall_s}


def _build_runtime(plan: PlanCandidate, cfg: ElasticConfig, mesh_cache,
                   params_host=None, opt_host=None):
    """Mesh + compiled step + placed state for one plan.  Returns the
    runtime dict and the measured build+compile seconds.

    The step is warmed on a throwaway init-state call (jit compiles at
    first execution, not construction) so restart compile time lands in
    the recovery account's ``compile_s`` instead of polluting the first
    resumed step's wall time (and the straggler detector)."""
    from repro.core.ffn import init_ffn, make_ffn_train_step
    from repro.data.synthetic import TeacherDataset
    from repro.launch.mesh import make_local_mesh
    from repro.optim import AdamW

    t0 = time.perf_counter()
    key = (plan.dp, plan.tp, plan.pp)
    if key not in mesh_cache:
        mesh_cache[key] = make_local_mesh(*key)
    mesh = mesh_cache[key]
    mcfg = plan.model_config()
    opt = AdamW(cfg.lr, weight_decay=0.0)
    step_fn, decls, opt_decls = make_ffn_train_step(mcfg, mesh, opt,
                                                    cfg.batch)
    if params_host is None:
        params, opt_state = init_ffn(mcfg, mesh, opt, seed=cfg.seed)
    else:
        params = place_host_tree(params_host, decls, mesh)
        opt_state = (place_host_tree(opt_host, opt_decls, mesh)
                     if opt_host is not None else opt.init(params))
    # warm the executable on a donated throwaway copy of the init state
    dummy_p, dummy_o = init_ffn(mcfg, mesh, opt, seed=cfg.seed)
    xw, yw = TeacherDataset(cfg.width, cfg.batch, seed=cfg.seed)(0)
    out = step_fn(dummy_p, dummy_o, jnp.int32(0), xw, yw)
    jax.block_until_ready(out[2])
    rt = {"mesh": mesh, "cfg": mcfg, "opt": opt, "step_fn": step_fn,
          "decls": decls, "opt_decls": opt_decls,
          "params": params, "opt_state": opt_state}
    return rt, time.perf_counter() - t0


def run_elastic(cfg: ElasticConfig, *, ledger=None,
                fault_script: Optional[FaultScript] = None,
                calibration=None, watchdog=None,
                log_fn=print) -> ElasticResult:
    """Train to ``cfg.target_loss`` through scripted device losses.

    Detection → policy → flush → re-plan (audited) → restore/convert →
    resume; the returned ``ElasticResult.account`` is the priced
    recovery account (also recorded to ``ledger``, kind ``elastic``)."""
    from repro.data.synthetic import TeacherDataset
    from repro.planner.calibration import calibrate_from_ledger

    os.makedirs(cfg.workdir, exist_ok=True)
    if cfg.devices % cfg.hosts:
        raise ValueError(f"{cfg.devices} devices do not divide over "
                         f"{cfg.hosts} hosts")
    devices_per_host = cfg.devices // cfg.hosts
    calib = calibration or calibrate_from_ledger()
    cluster = SimulatedCluster(os.path.join(cfg.workdir, "hb"),
                               hosts=cfg.hosts,
                               timeout_s=cfg.heartbeat_timeout_s,
                               virtual=True)
    mgr = CheckpointManager(os.path.join(cfg.workdir, "ckpt"),
                            keep=cfg.keep_checkpoints)
    policy = RestartPolicy(max_restarts=cfg.max_restarts)
    detector = StragglerDetector(window=cfg.straggler_window,
                                 threshold=cfg.straggler_threshold)
    ds = TeacherDataset(cfg.width, cfg.batch, seed=cfg.seed)
    meter = StepMeter(f"elastic_ffn{cfg.width}", warmup=1)
    mesh_cache: dict = {}
    fault_script = fault_script or FaultScript()
    tracer = get_tracer()
    metrics = get_metrics()

    run_span = tracer.begin("elastic/run", cat="elastic",
                            devices=cfg.devices, width=cfg.width)
    with tracer.span("elastic/plan", cat="elastic",
                     devices=cfg.devices) as sp:
        scored, _ = solve_plan(
            cfg.devices, cfg, calib, mesh_cache=mesh_cache,
            strategies=((cfg.initial_strategy,) if cfg.initial_strategy
                        else None))
        sp.annotate(plan=scored.plan.name)
    log_fn(f"[elastic] initial plan {scored.plan.name} "
           f"({scored.plan.devices} devices)")
    # every _build_runtime is an elastic/compile span: the recovery
    # account's compile_s sums phase-0 AND restart builds
    with tracer.span("elastic/compile", cat="elastic",
                     plan=scored.plan.name):
        rt, compile_s = _build_runtime(scored.plan, cfg, mesh_cache)
    phases: List[_Phase] = [_Phase(scored, 0, 0, compile_s,
                                   restart=False)]
    recoveries: List[dict] = []
    handled_dead: set = set()
    step = 0
    loss = float("nan")
    losses: List[float] = []
    reached = False
    aborted = False
    replay_until = 0               # steps below this re-run lost work
    phases[-1].io0 = (mgr.io_seconds, mgr.io_bytes)

    fired: set = set()
    while step < cfg.max_steps:
        for host in fault_script.hosts_at(step):
            if (step, host) in fired:
                continue    # a rewind replays the step; the host is
            fired.add((step, host))   # already dead
            cluster.kill(host)
            log_fn(f"[elastic] step {step}: host {host} lost")
        cluster.advance(cfg.virtual_dt)
        cluster.tick(step)
        new_dead = [h for h in cluster.check() if h not in handled_dead]
        if new_dead:
            handled_dead.update(new_dead)
            tracer.instant("elastic/detect", cat="elastic", step=step,
                           dead_hosts=sorted(new_dead))
            metrics.counter(
                "elastic_host_failures_total",
                "hosts declared dead by the heartbeat monitor").inc(
                    len(new_dead))
            mgr.flush(raise_errors=False)   # join any in-flight save
            phases[-1].close(mgr)
            decision = policy.on_host_failure(new_dead, None)
            survivors = cfg.hosts - len(handled_dead)
            alive = devices_per_host * survivors
            if decision == "abort" or alive < 1:
                log_fn(f"[elastic] step {step}: {decision if alive else 'no survivors'}"
                       f" ({len(handled_dead)}/{cfg.hosts} hosts dead)")
                aborted = True
                break
            with tracer.span("elastic/replan", cat="elastic",
                             alive_devices=alive) as sp:
                t_replan = time.perf_counter()
                new_scored, _ = solve_plan(alive, cfg, calib,
                                           mesh_cache=mesh_cache)
                replan_s = time.perf_counter() - t_replan
                sp.annotate(plan=new_scored.plan.name)
            with tracer.span("elastic/restore", cat="elastic") as sp:
                t_restore = time.perf_counter()
                latest = mgr.latest_step()
                params_host = opt_host = None
                distilled = False
                restored_step = 0
                if latest is not None:
                    index, flat = mgr.load_host(latest)
                    restored_step = int(index["step"])
                    nested = _nest(flat)
                    meta_plan = index.get("meta", {}).get("plan")
                    plan_old = (plan_from_dict(meta_plan) if meta_plan
                                else phases[-1].plan)
                    params_host, opt_host, distilled = convert_ffn_params(
                        plan_old, new_scored.plan,
                        nested.get("params", {}),
                        nested.get("opt") or None)
                    mgr.invalidate_after(restored_step)
                restore_s = time.perf_counter() - t_restore
                sp.annotate(distilled=distilled,
                            restored_step=restored_step)
            with tracer.span("elastic/compile", cat="elastic",
                             plan=new_scored.plan.name):
                rt, compile_s = _build_runtime(
                    new_scored.plan, cfg, mesh_cache, params_host,
                    opt_host)
            replayed = max(step - restored_step, 0)
            recoveries.append({
                "detect_step": step, "restored_step": restored_step,
                "dead_hosts": sorted(handled_dead),
                "devices_before": phases[-1].plan.devices,
                "devices_after": new_scored.plan.devices,
                "plan_before": phases[-1].plan.name,
                "plan_after": new_scored.plan.name,
                "replayed_steps": replayed, "distilled": distilled,
                "from_scratch": latest is None,
                "restore_s": restore_s, "replan_s": replan_s,
                "decision": decision,
                "audit_ok": bool(new_scored.notes.get("audit_ok",
                                                      not cfg.audit_replan)),
            })
            log_fn(f"[elastic] step {step}: re-planned onto "
                   f"{new_scored.plan.name} ({new_scored.plan.devices} of "
                   f"{alive} surviving devices), restored "
                   f"step {restored_step}"
                   + (" [distilled]" if distilled else "")
                   + f", replaying {replayed} step(s)")
            metrics.counter(
                "elastic_recoveries_total",
                "elastic re-plan/restore/resume cycles").inc(
                    distilled=str(distilled).lower())
            phases.append(_Phase(new_scored, restored_step, replayed,
                                 compile_s, restart=True))
            phases[-1].io0 = (mgr.io_seconds, mgr.io_bytes)
            replay_until = step
            step = restored_step
            continue

        x, y = ds(step)
        step_fn = rt["step_fn"]
        slow = step in cfg.slow_steps
        if slow and not cfg.virtual_step_time:
            base = (watchdog.reference_s()
                    if watchdog is not None else None)
            if not base:
                base = meter.median_us() * 1e-6 or 0.02
            delay = base * max(cfg.slow_factor - 1.0, 0.0)

            def step_fn(p, o, s, xx, yy, _inner=rt["step_fn"],
                        _delay=delay):
                out = _inner(p, o, s, xx, yy)
                jax.block_until_ready(out[2])
                time.sleep(_delay)   # the injected anomaly
                return out

        def run_metered(_fn=step_fn, _step=step, _x=x, _y=y):
            return meter.call(_fn, rt["params"], rt["opt_state"],
                              jnp.int32(_step), _x, _y)

        with tracer.span("elastic/step", cat="train", step=step,
                         plan=phases[-1].plan.name,
                         replay=step < replay_until):
            if watchdog is not None and watchdog.capture_pending():
                out = watchdog.capture(run_metered)
            else:
                out = run_metered()
        rt["params"], rt["opt_state"], loss_dev = out
        loss = float(loss_dev)
        losses.append(loss)
        phases[-1].steps += 1
        step += 1
        dt_s = meter.times_us[-1] / 1e6
        if cfg.virtual_step_time:
            dt_s = cfg.virtual_dt * (cfg.slow_factor if slow else 1.0)
        metrics.counter("train_steps_total",
                        "executed training steps").inc(
                            suite="elastic")
        metrics.histogram("train_step_seconds",
                          "metered train step wall seconds").observe(
                              dt_s, suite="elastic")
        metrics.gauge("train_loss", "last observed training loss").set(
            loss, suite="elastic")
        if watchdog is not None:
            # step already advanced: the anomaly row must name the
            # step that actually ran (the one --slow-step injects at)
            watchdog.observe(step - 1, dt_s)
        straggle = note_step_time(
            detector, policy, step, dt_s, ledger,
            name="elastic_straggler", arch=f"ffn{cfg.width}",
            impl=phases[-1].plan.strategy, p=phases[-1].plan.tp)
        save_now = (step % cfg.checkpoint_every == 0
                    or straggle == "checkpoint")
        if save_now:
            mgr.save_async(step, rt["params"], rt["opt_state"],
                           meta={"plan": phases[-1].plan.as_dict()})
        if loss <= cfg.target_loss:
            reached = True
            break

    mgr.flush(raise_errors=False)
    if not aborted:
        phases[-1].close(mgr)
    phase_dicts = [p.as_dict() for p in phases]
    account = recovery_account(phase_dicts, recoveries)
    account["target_loss"] = cfg.target_loss
    account["reached_target"] = reached
    result = ElasticResult(
        reached_target=reached, aborted=aborted, final_loss=loss,
        final_step=step, phases=phase_dicts, recoveries=recoveries,
        account=account, plan_names=[p.plan.name for p in phases],
        losses=losses)
    entry = None
    if ledger is not None:
        last = phases[-1].plan
        entry = ledger.record(LedgerEntry(
            name=f"elastic_ffn{cfg.width}", suite="elastic",
            kind="elastic", arch=f"ffn{cfg.width}x{cfg.depth}",
            impl=last.strategy, p=last.tp,
            measured=dict(meter.summary(), final_loss=loss,
                          steps=step, wall_s=account["wall_s"]),
            predicted={"energy_j_total": account["energy_j_total"],
                       "energy_j_useful": account["energy_j_useful"],
                       "energy_j_replay": account["energy_j_replay"]},
            extra={"recovery": account, "phases": phase_dicts,
                   "recoveries": recoveries,
                   "plans": [p.plan.name for p in phases],
                   "reached_target": reached, "aborted": aborted,
                   "target_loss": cfg.target_loss,
                   "straggler_flags": len(detector.flagged)}))
        ledger.flush()
    if entry is not None:
        run_span.link_ledger(entry)
    run_span.annotate(final_step=step, reached_target=reached,
                      recoveries=len(recoveries))
    tracer.end(run_span)
    log_fn(f"[elastic] done: step {step} loss {loss:.4f} "
           f"target {'REACHED' if reached else 'missed'}, "
           f"{len(recoveries)} recovery(ies), replay ratio "
           f"{account['replay_overhead_ratio']:.3f}")
    return result
