"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-14b \
      --smoke --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-14b \
      --plan auto          # apply the planner's winning configuration

Full (non-smoke) configs target the production TPU mesh; on this CPU
container they are exercised through the dry-run
(``python -m repro.launch.dryrun``), so --smoke is the default here.
On a real multi-host TPU deployment this same entry point is launched
once per host after ``jax.distributed.initialize()`` (see README).

``--plan auto`` reads ``PLAN_report.json`` (running a quick calibrated
no-pilot planning pass over the --dp × --tp device budget if the report
doesn't exist yet) and applies the winning plan: its ``ProjectionSpec``
becomes the config's default projection for every site, and the mesh
becomes the winner's (dp, tp).  ``--plan <path>`` applies a specific
report.  See docs/planner.md.

``--elastic`` switches to the elastic fault-tolerant runtime
(docs/elastic.md): paper-FFN training on a simulated multi-host cluster
with async checkpointing, heartbeat failure detection, and energy-aware
re-planning of dp×tp×pp×k over the survivors.  ``--kill-at-step N
--kill-host hostK`` injects a deterministic device-loss event:

  PYTHONPATH=src python -m repro.launch.train --elastic \
      --devices 8 --hosts 4 --kill-at-step 25 --kill-host host3

The run must survive the loss, re-plan onto an audit-clean surviving
mesh, restore from the latest checkpoint and reach --target-loss; the
recovery energy account (replayed steps, checkpoint IO, restart) lands
in ``BENCH_report.json``.  Exit code reflects success.
"""
import argparse
import os
import sys


def _apply_plan(args, cfg):
    """Resolve --plan (auto | path) to a winner and apply it."""
    import repro.launch.plan as plan_cli
    from repro.configs.base import (PHANTOM_KINDS, ProjectionMap,
                                    ProjectionSpec)
    from repro.planner import load_plan_report

    path = plan_cli.DEFAULT_OUT if args.plan == "auto" else args.plan
    if os.path.exists(path):
        report = load_plan_report(path)
        print(f"[plan] loaded {path}")
    elif args.plan == "auto":
        pargs = plan_cli.build_parser().parse_args(
            ["--devices", str(args.dp * args.tp), "--no-pilots",
             "--out", path])
        report = plan_cli.plan(pargs)
        print(f"[plan] no report found — ran a no-pilot planning pass")
    else:
        raise FileNotFoundError(f"--plan {args.plan}: no such report")
    winner = report.get("winner")
    if not winner:
        raise ValueError(f"{path}: empty frontier, no winning plan")
    p = winner["plan"]
    budget = args.dp * args.tp * max(args.pp, 1)
    if p["devices"] > budget:
        # the XLA host device count was already pinned from --dp/--tp;
        # silently clamping the winner's mesh would train a different
        # configuration than the one we just announced
        raise ValueError(
            f"winning plan {p['name']} needs {p['devices']} devices but "
            f"--dp {args.dp} x --tp {args.tp} x --pp {args.pp} only "
            f"provisioned {budget}; re-run with --dp/--tp/--pp covering "
            f"the plan's mesh ({p['dp']}x{p['tp']}x{p.get('pp', 1)}pp)")
    spec = p.get("projection_spec", {})
    kind = spec.get("kind", p.get("strategy", "tensor"))
    if kind in PHANTOM_KINDS:
        default = ProjectionSpec(kind=kind, k=int(spec.get("k", 64)),
                                 variant=spec.get("variant", "fused"))
        applied = f"{kind} k={default.k}"
    else:
        # any tensor-family winner means "dense TP": the planner scored
        # one square FFN site, while an architecture mixes input-side
        # (column) and output-side (row) projections — the ``tensor``
        # pseudo-kind resolves each site to its natural dense sharding,
        # which is what the winner's strategy family prescribes
        default = ProjectionSpec(kind="tensor")
        applied = f"{kind} -> site-natural dense sharding"
    cfg = cfg.replace(projections=ProjectionMap(default=default))
    pp = int(p.get("pp", 1))
    print(f"[plan] applying winner {p['name']}: projections default="
          f"{applied}, mesh {p['dp']}x{p['tp']}"
          + (f"x{pp}pp" if pp > 1 else ""))
    return cfg, p["dp"], p["tp"], pp


def run_elastic_cli(args) -> int:
    """The --elastic entry point: paper-FFN elastic training with
    scripted fault injection; returns a process exit code (0 iff the
    run survived its faults and reached --target-loss)."""
    import tempfile

    from repro.obs import EnergyDriftWatchdog
    from repro.telemetry import Ledger
    from repro.train.elastic import ElasticConfig, run_elastic
    from repro.train.fault import FaultScript

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    report_out = args.report_out or os.path.join(root, "BENCH_report.json")
    jsonl = os.path.join(os.path.dirname(report_out) or ".",
                         "BENCH_ledger.jsonl")

    kills = []
    steps = args.kill_at_step or []
    names = args.kill_host or []
    for i, s in enumerate(steps):
        # unnamed kills default to the highest-numbered hosts first
        host = (names[i] if i < len(names)
                else f"host{args.hosts - 1 - i}")
        kills.append((s, host))

    cfg = ElasticConfig(
        workdir=args.workdir or tempfile.mkdtemp(prefix="elastic_"),
        devices=args.devices, hosts=args.hosts, width=args.width,
        depth=args.depth, batch=args.batch, target_loss=args.target_loss,
        max_steps=args.steps, checkpoint_every=args.ckpt_every,
        slow_steps=tuple(args.slow_step or ()),
        slow_factor=args.slow_factor)
    ledger = Ledger(run="launch.train.elastic", jsonl_path=jsonl)
    profile_dir = args.profile_dir
    if profile_dir is None and cfg.slow_steps:
        profile_dir = os.path.join(cfg.workdir, "profile")
    watchdog = EnergyDriftWatchdog(
        ledger=ledger, profile_dir=profile_dir,
        name=f"elastic_ffn{cfg.width}", arch=f"ffn{cfg.width}")
    res = run_elastic(cfg, ledger=ledger, watchdog=watchdog,
                      fault_script=FaultScript(kills=tuple(kills)))
    ledger.write_report(report_out)
    acct = res.account
    print(f"[elastic] report -> {report_out}")
    print(f"[elastic] energy_j_total {acct['energy_j_total']:.3e} "
          f"(useful {acct['energy_j_useful']:.3e}, "
          f"replay {acct['energy_j_replay']:.3e}, "
          f"ckpt_io {acct['energy_j_ckpt_io']:.3e}, "
          f"restart {acct['energy_j_restart']:.3e}); "
          f"replay_overhead {acct['replay_overhead_ratio']:.3f}")
    wd = watchdog.summary()
    print(f"[obs] watchdog: {len(wd['trips'])} trip(s) over "
          f"{wd['observations']} observation(s)"
          + (f", profiler capture -> {wd['captures'][-1]}"
             if wd["captures"] else ""))
    if res.aborted:
        print("[elastic] FAILED: run aborted")
        return 2
    if not res.reached_target:
        print(f"[elastic] FAILED: final loss {res.final_loss:.4f} > "
              f"target {cfg.target_loss}")
        return 1
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--impl", default="phantom",
                    choices=["dense", "phantom"])
    ap.add_argument("--steps", type=int, default=None,
                    help="train steps (default 100; 300 with --elastic)")
    ap.add_argument("--batch", type=int, default=None,
                    help="global batch (default 8; 32 with --elastic)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages (adds a 'pipe' mesh axis and "
                         "runs the 1F1B schedule; layer count must "
                         "divide by it)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--plan", default=None,
                    help="'auto' or a PLAN_report.json path: apply the "
                         "energy planner's winning configuration "
                         "(projections + mesh)")
    # --- elastic fault-tolerant runtime (docs/elastic.md) ---
    ap.add_argument("--elastic", action="store_true",
                    help="run the elastic fault-tolerant paper-FFN "
                         "runtime with energy-aware re-planning")
    ap.add_argument("--devices", type=int, default=8,
                    help="[elastic] total device budget")
    ap.add_argument("--hosts", type=int, default=4,
                    help="[elastic] simulated hosts (devices%%hosts==0)")
    ap.add_argument("--kill-at-step", type=int, action="append",
                    default=None, metavar="N",
                    help="[elastic] inject a host loss at step N "
                         "(repeatable)")
    ap.add_argument("--kill-host", action="append", default=None,
                    metavar="HOST",
                    help="[elastic] which host dies at the matching "
                         "--kill-at-step (default hostH, last first)")
    ap.add_argument("--target-loss", type=float, default=0.12,
                    help="[elastic] stop when teacher loss reaches this")
    ap.add_argument("--width", type=int, default=64,
                    help="[elastic] paper-FFN width")
    ap.add_argument("--depth", type=int, default=2,
                    help="[elastic] paper-FFN depth")
    ap.add_argument("--ckpt-every", type=int, default=10,
                    help="[elastic] checkpoint cadence (steps)")
    ap.add_argument("--workdir", default=None,
                    help="[elastic] checkpoint/heartbeat dir "
                         "(default: a temp dir)")
    ap.add_argument("--report-out", default=None,
                    help="[elastic] write the energy ledger report here "
                         "(default: repo-root BENCH_report.json)")
    # --- observability (docs/observability.md) ---
    from repro.launch.obs import add_obs_args, obs_session
    add_obs_args(ap)
    ap.add_argument("--slow-step", type=int, action="append",
                    default=None, metavar="N",
                    help="[elastic] inject a watchdog-visible slow step "
                         "at step N (repeatable)")
    ap.add_argument("--slow-factor", type=float, default=6.0,
                    help="[elastic] slowdown factor for --slow-step")
    ap.add_argument("--profile-dir", default=None,
                    help="watchdog jax.profiler capture dir (default: "
                         "<workdir>/profile when --slow-step is given)")
    ap.add_argument("--kernel-backend", default=None,
                    choices=("xla", "pallas", "auto"),
                    help="executing kernel for the phantom fused "
                         "projection and the attention core (docs/"
                         "kernels.md); default: the config's per-site "
                         "specs (xla)")
    ap.add_argument("--overlap", default=None, choices=("tpu", "gpu"),
                    help="append the async-collective + latency-hiding-"
                         "scheduler flag recipe for the given platform "
                         "(LIBTPU_INIT_ARGS on tpu, XLA_FLAGS on gpu): "
                         "comm/compute overlap of the ghost all-gather")
    args = ap.parse_args()
    if args.overlap:
        from repro.parallel.compat import enable_comm_overlap
        applied = enable_comm_overlap(args.overlap)
        print(f"[train] comm/compute overlap flags: {applied or '(set)'}")
    if args.steps is None:
        args.steps = 300 if args.elastic else 100
    if args.batch is None:
        args.batch = 32 if args.elastic else 8

    if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        ndev = (args.devices if args.elastic
                else args.dp * args.tp * max(args.pp, 1))
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={ndev} "
            + os.environ.get("XLA_FLAGS", ""))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.elastic:
        with obs_session(args.trace_out, args.metrics_out,
                         meta={"run": "launch.train.elastic"}):
            rc = run_elastic_cli(args)
        sys.exit(rc)

    from repro.configs.base import ShapeConfig, get_config
    from repro.data.synthetic import LMDataset
    from repro.launch.mesh import make_local_mesh, make_production_mesh
    from repro.launch.specs import input_specs
    from repro.optim import make_optimizer
    from repro.optim.schedules import warmup_cosine
    from repro.parallel.axes import MeshAxes
    from repro.train.trainer import Trainer

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.plan:
        cfg, args.dp, args.tp, args.pp = _apply_plan(args, cfg)
    elif args.impl == "dense":
        from repro.configs.base import dense_projection_map
        cfg = cfg.replace(projections=dense_projection_map())
    if args.kernel_backend:
        from repro.configs.base import with_kernel_backend
        cfg = with_kernel_backend(cfg, args.kernel_backend)
    mesh = (make_local_mesh(args.dp, args.tp, args.pp) if args.smoke
            else make_production_mesh(pp=args.pp))
    axes = MeshAxes.from_mesh(mesh)
    if axes.pp > 1:
        print(f"[train] 1F1B pipeline: pp={axes.pp} stages x dp={axes.dp} "
              f"x tp={axes.tp}, {args.microbatches} microbatch(es)")
    _, bspec = input_specs(
        cfg, ShapeConfig("cli", args.seq, args.batch, "train"), axes)
    opt = make_optimizer(cfg.optimizer,
                         warmup_cosine(3e-4, 20, args.steps),
                         weight_decay=0.1)
    ds = LMDataset(cfg.vocab_size, args.batch, args.seq + 1)
    with obs_session(args.trace_out, args.metrics_out,
                     meta={"run": "launch.train", "arch": args.arch}):
        from repro.obs import EnergyDriftWatchdog
        watchdog = (EnergyDriftWatchdog(profile_dir=args.profile_dir,
                                        name=f"train_{cfg.name}",
                                        arch=cfg.name)
                    if args.profile_dir else None)
        trainer = Trainer(cfg, mesh, opt, ds, batch_spec=bspec,
                          microbatches=args.microbatches,
                          checkpoint_dir=args.ckpt_dir,
                          watchdog=watchdog)
        state = trainer.restore_or_init()
        trainer.run(state, args.steps)


if __name__ == "__main__":
    main()
