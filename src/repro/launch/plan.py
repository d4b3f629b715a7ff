"""Energy-aware configuration planner CLI.

  PYTHONPATH=src python -m repro.launch.plan --devices 8 --target-loss 0.2

Calibrates the analytic energy model from ``BENCH_ledger.jsonl`` (paper
defaults when absent), enumerates mesh × strategy × ghost-width
candidates up to ``--devices``, filters for HBM fit and throughput,
runs small pilot training runs to normalize every plan to the target
loss (``--no-pilots`` skips them and prices plans at the calibrated
ν scales instead), and writes ``PLAN_report.json`` with the Pareto
frontier, the matched-loss phantom-vs-TP comparison, and the winning
plan.  ``python -m repro.launch.train --plan auto`` applies the winner.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_LEDGER = os.path.join(ROOT, "BENCH_ledger.jsonl")
DEFAULT_OUT = os.path.join(ROOT, "PLAN_report.json")


def build_parser():
    ap = argparse.ArgumentParser(
        prog="repro.launch.plan",
        description="calibrated search over mesh x strategy x ghost "
                    "width with an iso-loss frontier")
    ap.add_argument("--devices", type=int, default=8,
                    help="device budget (the FULL mesh TP plans use)")
    ap.add_argument("--target-loss", type=float, default=0.2,
                    help="the fixed loss every plan is normalized to")
    ap.add_argument("--width", type=int, default=1024,
                    help="base FFN width n (iso-loss pilots may shrink "
                         "per-strategy widths)")
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--ks", default="4,8,16",
                    help="comma-separated ghost widths to search")
    ap.add_argument("--strategies", default="tensor_col,phantom")
    ap.add_argument("--microbatches", default="1",
                    help="comma-separated gradient-accumulation options")
    ap.add_argument("--pps", default="1,2",
                    help="comma-separated pipeline-stage counts to "
                         "search (1 = no pipeline axis)")
    ap.add_argument("--hbm-gb", type=float, default=16.0,
                    help="per-device HBM budget (TPU v5e default)")
    ap.add_argument("--min-throughput", type=float, default=0.0,
                    help="global rows/s floor (0 = unconstrained)")
    ap.add_argument("--ledger", default=DEFAULT_LEDGER,
                    help="BENCH_ledger.jsonl to calibrate from")
    ap.add_argument("--no-pilots", action="store_true",
                    help="skip pilot runs; price plans at the "
                         "calibrated nu scales")
    ap.add_argument("--pilot-steps", type=int, default=300,
                    help="pilot iteration budget (also the censored nu)")
    ap.add_argument("--pilot-tp", type=int, default=4,
                    help="model-axis size the pilots train at")
    ap.add_argument("--compiled-hbm-check", action="store_true",
                    help="verify the frontier's HBM fit against the "
                         "lowered probe step (cached analysis)")
    ap.add_argument("--no-audit", dest="audit", action="store_false",
                    help="skip the static sharding/energy audit of the "
                         "frontier (on by default: a plan whose lowered "
                         "collectives don't match its priced account is "
                         "moved to rejected)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=DEFAULT_OUT)
    from repro.launch.obs import add_obs_args
    add_obs_args(ap)
    return ap


def _csv_ints(s):
    return tuple(int(x) for x in s.split(",") if x)


def plan(args, ledger=None, calib_rows=None) -> dict:
    """Run the full planning pass; returns the report dict (also
    written to ``args.out``).  ``ledger`` optionally receives
    pilot/frontier rows (the plan_smoke suite passes the shared
    benchmarks ledger); ``calib_rows`` calibrates from already-loaded
    ledger rows instead of the ``--ledger`` file (plan_smoke passes the
    in-process entries, since benchmarks.run truncates the JSONL stream
    at startup)."""
    from repro.launch.mesh import make_local_mesh
    from repro.planner import (Constraints, apply_iso_loss,
                               apply_throughput_floor, build_report,
                               calibrate_from_ledger, compiled_hbm_bytes,
                               enumerate_plans, filter_feasible,
                               matched_loss_comparison, pareto_frontier,
                               plan_summary_lines, record_frontier,
                               run_pilots, score_plans, write_plan_report)

    from repro.obs import get_tracer
    tracer = get_tracer()
    strategies = tuple(s for s in args.strategies.split(",") if s)
    ks = _csv_ints(args.ks)
    mbs = _csv_ints(args.microbatches)

    # 1. calibrate
    with tracer.span("plan/calibrate", cat="plan") as sp:
        if calib_rows is not None:
            from repro.planner import calibrate_from_rows
            calib = calibrate_from_rows(calib_rows)
            print(f"# calibration: {calib.source} "
                  f"(in-process ledger rows)")
        else:
            ledger_path = (args.ledger if os.path.exists(args.ledger)
                           else None)
            calib = calibrate_from_ledger(jsonl_path=ledger_path)
            print(f"# calibration: {calib.source}"
                  + (f" ({ledger_path})" if ledger_path else ""))
        sp.annotate(source=calib.source)

    # 2. enumerate + resource-filter
    constraints = Constraints(
        max_devices=args.devices,
        hbm_bytes_per_device=args.hbm_gb * 2 ** 30,
        min_throughput_rows_s=args.min_throughput)
    with tracer.span("plan/enumerate", cat="plan",
                     devices=args.devices) as sp:
        candidates = enumerate_plans(
            args.devices, width=args.width, depth=args.depth,
            batch=args.batch, strategies=strategies, ks=ks,
            microbatch_options=mbs, pps=_csv_ints(args.pps) or (1,))
        feasible, rejected = filter_feasible(candidates, constraints)
        sp.annotate(candidates=len(candidates), feasible=len(feasible))
    print(f"# {len(candidates)} candidates, {len(feasible)} feasible, "
          f"{len(rejected)} rejected")

    # 3. pilots -> iso-loss normalization
    iso = None
    if args.no_pilots:
        with tracer.span("plan/score", cat="plan"):
            scored = score_plans(feasible, calib,
                                 iterations=float(args.pilot_steps))
        for s in scored:
            s.predicted_loss = args.target_loss
            s.notes["iso_loss"] = False
    else:
        pilot_mesh = make_local_mesh(1, min(args.pilot_tp, args.devices))
        with tracer.span("plan/pilots", cat="plan",
                         strategies=list(strategies)):
            iso = run_pilots(strategies, pilot_mesh, width=args.width,
                             depth=args.depth, batch=args.batch,
                             steps=args.pilot_steps,
                             target_loss=args.target_loss, ks=ks,
                             seed=args.seed, ledger=ledger)
        for key, nu in sorted(iso.nu.items()):
            fl = iso.final_loss.get(key)
            print(f"# pilot {key}: nu={nu} final_loss="
                  f"{fl:.4f}" if fl is not None else f"# pilot {key}")
        for kind, curve in iso.curves.items():
            print(f"# pilot curve {kind}: loss(k) = "
                  f"exp({curve.a:.3f}) * k^{curve.b:.3f}")
        scored = apply_iso_loss(feasible, iso, calib)

    # 4. throughput floor + frontier + verdict (the verdict quantifies
    # over the SURVIVORS — a plan the floor rejected must not win it).
    # The frontier (and hence the winner) is drawn from the MATCHED
    # pool: a censored plan that never reached the target has a cheap
    # ν·e product but is not delivering the target loss — it must not
    # undercut plans that measurably did.
    scored_kept, thr_rejected = apply_throughput_floor(
        scored, args.min_throughput)

    def make_frontier(pool):
        m = [s for s in pool if s.notes.get("reached_target", True)]
        return pareto_frontier(m if m else pool)

    frontier = make_frontier(scored_kept)

    # ground-truth the frontier's HBM fit against the lowered probe
    # step (cached analysis); an over-budget plan is dropped and the
    # frontier recomputed so newly-exposed plans get checked too
    mesh_cache = {}
    if args.compiled_hbm_check:
        checked = set()
        while True:
            over = []
            for s in frontier:
                if id(s) in checked:
                    continue
                checked.add(id(s))
                key = (s.plan.dp, s.plan.tp, s.plan.pp)
                if key not in mesh_cache:
                    mesh_cache[key] = make_local_mesh(*key)
                got = compiled_hbm_bytes(s.plan, mesh_cache[key])
                s.notes["compiled_hbm_bytes"] = got
                if got is not None and \
                        got > constraints.hbm_bytes_per_device:
                    over.append(s)
            if not over:
                break
            for s in over:
                thr_rejected.append(
                    (s, f"compiled HBM {s.notes['compiled_hbm_bytes']/2**30:.2f} "
                        f"GiB > {args.hbm_gb:.2f} GiB budget"))
                scored_kept.remove(s)
            frontier = make_frontier(scored_kept)

    # static sharding & energy audit of the frontier: lower each
    # candidate's probe (through the shared telemetry caches — nothing
    # the HBM check compiled is re-lowered) and reject any plan whose
    # collectives don't reconcile with its priced CommEvent account.
    # Same recheck-loop shape as above: dropping a plan exposes new
    # frontier members, which must be audited too.
    audit_results = {}
    if getattr(args, "audit", True):
        from repro.analysis import audit_plans
        while True:
            todo = [s for s in frontier
                    if s.plan.name not in audit_results]
            if todo:
                audit_results.update(audit_plans(
                    [s.plan for s in todo], mesh_cache=mesh_cache))
            bad = [s for s in frontier
                   if not audit_results[s.plan.name]["ok"]]
            if not bad:
                break
            for s in bad:
                errs = audit_results[s.plan.name]["errors"]
                thr_rejected.append(
                    (s, f"static audit: {len(errs)} error(s), first: "
                        f"{errs[0] if errs else 'unlowerable'}"))
                scored_kept.remove(s)
            frontier = make_frontier(scored_kept)
        n_bad = sum(1 for r in audit_results.values() if not r["ok"])
        print(f"# audit: {len(audit_results)} frontier plans checked, "
              f"{n_bad} rejected")

    comparison = matched_loss_comparison(scored_kept, args.devices)
    if iso is not None and not comparison.get("matched_plans"):
        reachable = min(iso.final_loss.values(), default=float("nan"))
        print(f"# WARNING: no pilot reached --target-loss "
              f"{args.target_loss} within {args.pilot_steps} steps "
              f"(best final loss {reachable:.4f}); the matched-loss "
              f"comparison is empty — raise the target or "
              f"--pilot-steps", file=sys.stderr)

    report = build_report(
        calibration=calib, constraints=constraints, scored=scored_kept,
        frontier=frontier, rejected=rejected,
        throughput_rejected=thr_rejected, iso=iso, comparison=comparison,
        meta={"argv": vars(args), "target_loss": args.target_loss,
              "devices": args.devices})
    if audit_results:
        report["audit"] = audit_results
    if ledger is not None:
        record_frontier(ledger, frontier, calib)
    write_plan_report(report, args.out)
    print("\n".join(plan_summary_lines(report)))
    print(f"# wrote {args.out} ({len(frontier)} frontier plans)")
    return report


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.launch.obs import obs_session
    with obs_session(args.trace_out, args.metrics_out,
                     meta={"run": "launch.plan"}):
        report = plan(args)
    return 0 if report["frontier"] else 1


if __name__ == "__main__":
    sys.exit(main())
