"""Serving launcher: the energy-aware serving runtime over the mesh.

Fixed config, closed trace (the classic smoke run):

  PYTHONPATH=src python -m repro.launch.serve --arch chatglm3-6b \
      --requests 8

Routed: price tensor/phantom x mesh x slots candidates in predicted
joules-per-token with the planner's calibrated constants, pick the
cheapest meeting the SLO, replay a synthetic trace through it and print
the measured TTFT/TPOT/e2e percentiles + the energy ledger join:

  PYTHONPATH=src python -m repro.launch.serve --route auto \
      --trace poisson --slo 200ms

``--ledger PATH`` streams the serve telemetry rows to a JSONL file (and
prints the joined ratios); ``--sample "t=0.8,k=40,p=0.95"`` switches
the whole trace from greedy to seeded sampling; ``--seed`` seeds both
the trace and the prompt token streams.  docs/serving.md documents the
runtime and the joules-per-token methodology.
"""
import argparse
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_LEDGER_SRC = os.path.join(ROOT, "BENCH_ledger.jsonl")
DEFAULT_PLAN = os.path.join(ROOT, "PLAN_report.json")
DEFAULT_ROUTE_OUT = os.path.join(ROOT, "SERVE_route.json")
DEFAULT_REPORT = os.path.join(ROOT, "BENCH_report.json")


def parse_slo_ms(text):
    """'200ms' | '0.2s' | '200' (ms) -> float ms; None/'' -> 0."""
    if not text:
        return 0.0
    m = re.fullmatch(r"\s*([\d.]+)\s*(ms|s)?\s*", str(text))
    if not m:
        raise argparse.ArgumentTypeError(f"bad SLO {text!r} "
                                         "(want e.g. 200ms or 0.2s)")
    val = float(m.group(1))
    return val * 1e3 if m.group(2) == "s" else val


def parse_sampling(text):
    """'t=0.8,k=40,p=0.95' -> SamplingParams; ''/None -> greedy."""
    from repro.serve.sampling import SamplingParams
    if not text:
        return None
    kw = {}
    keys = {"t": "temperature", "temperature": "temperature",
            "k": "top_k", "top_k": "top_k",
            "p": "top_p", "top_p": "top_p", "seed": "seed"}
    for part in str(text).split(","):
        if not part.strip():
            continue
        k, _, v = part.partition("=")
        k = k.strip().lower()
        if k not in keys:
            raise argparse.ArgumentTypeError(
                f"bad --sample key {k!r} (known: t/k/p/seed)")
        field = keys[k]
        kw[field] = int(v) if field in ("top_k", "seed") else float(v)
    kw.setdefault("temperature", 0.8)
    return SamplingParams(**kw)


def build_parser():
    ap = argparse.ArgumentParser(
        prog="repro.launch.serve",
        description="continuous-batching serving with paged KV cache, "
                    "traffic/SLO harness and joules-per-token routing")
    ap.add_argument("--arch", default="chatglm3-6b")
    ap.add_argument("--requests", type=int, default=None,
                    help="trace length (default 8; fleet mode defaults "
                         "to 100000 modeled / 64 executed)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0,
                    help="trace + prompt RNG seed")
    ap.add_argument("--ledger", default="",
                    help="stream serve telemetry rows to this JSONL "
                         "path (standalone sessions record like run())")
    ap.add_argument("--trace", default="",
                    choices=["", "poisson", "bursty", "closed"],
                    help="synthetic workload; empty = legacy closed "
                         "batch of --requests equal prompts")
    ap.add_argument("--rate", type=float, default=None,
                    help="trace arrival rate in requests/s (default "
                         "4.0; fleet mode auto-sizes to the decode "
                         "pool's modeled capacity)")
    ap.add_argument("--slo", type=parse_slo_ms, default=0.0,
                    help="TTFT/TPOT SLO, e.g. 200ms")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request e2e deadline for goodput")
    ap.add_argument("--sample", default="",
                    help="sampling params, e.g. 't=0.8,k=40,p=0.95' "
                         "(default greedy)")
    ap.add_argument("--route", default="fixed",
                    choices=["fixed", "auto"],
                    help="auto: price candidates in predicted J/token "
                         "and serve the cheapest meeting --slo")
    ap.add_argument("--order", default="fcfs", choices=["fcfs", "edf"])
    ap.add_argument("--calibration", default=DEFAULT_PLAN,
                    help="PLAN_report.json with fitted constants "
                         "(falls back to BENCH_ledger.jsonl, then "
                         "paper defaults)")
    ap.add_argument("--route-out", default=DEFAULT_ROUTE_OUT,
                    help="persist the --route auto candidate J/token "
                         "table here as serve-route/v1 JSON "
                         "('' disables)")
    fleet = ap.add_argument_group("fleet (disaggregated serving)")
    fleet.add_argument("--fleet", action="store_true",
                       help="disaggregated prefill/decode fleet replay "
                            "with J/token autoscaling (modeled "
                            "discrete-event run by default)")
    fleet.add_argument("--executed", action="store_true",
                       help="fleet with real jitted engines (small "
                            "traces; proves token-exactness)")
    fleet.add_argument("--colocated", action="store_true",
                       help="run the single-engine baseline through "
                            "the fleet simulator instead")
    fleet.add_argument("--prefill-replicas", type=int, default=1,
                       help="initial prefill pool size")
    fleet.add_argument("--decode-replicas", type=int, default=1,
                       help="initial decode pool size")
    fleet.add_argument("--route-table", default=DEFAULT_ROUTE_OUT,
                       help="serve-route/v1 JSON the fleet planner "
                            "consumes when present (else it prices "
                            "candidates fresh)")
    fleet.add_argument("--report-out", default=DEFAULT_REPORT,
                       help="fleet mode: write the ledger report here")
    from repro.launch.obs import add_obs_args
    add_obs_args(ap)
    return ap


def _print_slo(report):
    for key in ("ttft_ms", "tpot_ms", "e2e_ms"):
        pc = report.get(key) or {}
        if pc:
            print(f"{key:8s} p50={pc['p50']:8.2f}  p95={pc['p95']:8.2f}  "
                  f"p99={pc['p99']:8.2f}  (ms)")
    print(f"requests={report.get('requests', 0)} "
          f"tokens={report.get('generated_tokens', 0)} "
          f"slo_met={report.get('slo_met_fraction', 0.0):.0%} "
          f"goodput_tokens={report.get('goodput_tokens', 0)}")


def main(argv=None):
    args = build_parser().parse_args(argv)

    if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.dp * args.tp} "
            + os.environ.get("XLA_FLAGS", ""))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.launch.obs import obs_session
    with obs_session(args.trace_out, args.metrics_out,
                     meta={"run": "launch.serve", "arch": args.arch}):
        return _main(args)


def _main(args):
    from repro.planner import load_calibration
    from repro.serve.router import (ServeConfig, candidate_configs, route,
                                    run_config)
    from repro.serve.traffic import make_trace, TraceItem
    from repro.telemetry import Ledger

    calib = load_calibration(plan_report_path=args.calibration,
                             ledger_path=DEFAULT_LEDGER_SRC)
    sampling = parse_sampling(args.sample)

    if args.fleet:
        return _fleet_main(args, calib, sampling)

    ledger = None
    if args.ledger:
        ledger = Ledger(run="launch.serve", jsonl_path=args.ledger)

    n_requests = args.requests if args.requests is not None else 8
    rate = args.rate if args.rate is not None else 4.0
    if args.trace:
        trace = make_trace(args.trace, n=n_requests,
                           rate_rps=rate,
                           prompt_len_range=(4, min(48, args.max_len - 1)),
                           new_tokens_range=(4, args.new_tokens),
                           deadline_ms=args.deadline_ms, seed=args.seed)
    else:
        # legacy closed batch: --requests equal 16-token prompts
        trace = [TraceItem(arrival_s=0.0, prompt_len=16,
                           max_new_tokens=args.new_tokens,
                           deadline_ms=args.deadline_ms, seed=args.seed)
                 for _ in range(n_requests)]

    if args.route == "auto":
        cands = candidate_configs(args.arch, args.dp * args.tp,
                                  slots_options=(args.slots,),
                                  max_len=args.max_len,
                                  page_size=args.page_size)
        winner, priced = route(cands, calib, trace, slo_ms=args.slo)
        print(f"# calibration: {calib.source}")
        print("# candidates (predicted, modeled accelerator):")
        for pc in priced:
            flag = "*" if pc is winner else " "
            print(f"# {flag} {pc.config.name:<44s} "
                  f"J/tok={pc.j_per_token:.3e} "
                  f"ttft={pc.ttft_s*1e3:.3f}ms tpot={pc.tpot_s*1e3:.3f}ms "
                  f"slo_ok={pc.meets_slo}")
        sc = winner.config
        print(f"# routed -> {sc.name} "
              f"(predicted {winner.j_per_token:.3e} J/token)")
        if args.route_out:
            from repro.serve.fleet import write_route_table
            from repro.serve.router import trace_stats
            write_route_table(
                args.route_out, args.arch, winner, priced,
                calibration=calib.source,
                stats=trace_stats(trace, args.page_size),
                slo_ms=args.slo)
            print(f"# route table ({len(priced)} candidates) -> "
                  f"{args.route_out}")
    else:
        impl = "phantom" if "phantom" in args.arch else "tensor"
        sc = ServeConfig(args.arch, impl, args.dp, args.tp, args.slots,
                         max_len=args.max_len, page_size=args.page_size)

    result = run_config(sc, trace, ledger=ledger, calib=calib,
                        seed=args.seed, slo_ms=args.slo,
                        sampling=sampling, order=args.order)
    print(f"# served {sc.name} on mesh {sc.dp}x{sc.tp}")
    _print_slo(result["slo"])
    ratio = result["energy_ratio"]
    print(f"joules/token (measured HLO account): "
          f"{result['j_per_token_measured']:.3e}")
    for kind in ("prefill", "decode"):
        if kind in ratio:
            print(f"energy measured/predicted [{kind}]: "
                  f"{ratio[kind]:.3f}")
    pages = result["pages"]
    print(f"pages: high_water={pages['high_water_pages']}"
          f"/{pages['total_pages']} allocs={pages['page_allocs']} "
          f"frees={pages['page_frees']} "
          f"fragmentation={pages['fragmentation']:.2f}")
    if ledger is not None:
        print(f"# wrote {len(ledger)} ledger rows to {args.ledger}")
    return 0


def _fleet_main(args, calib, sampling):
    """Disaggregated fleet replay (docs/serving.md, "Fleet")."""
    from repro.serve.fleet import (FleetConfig, FleetRouter,
                                   auto_rate_rps, baseline_config,
                                   load_route_table, plan_pools)
    from repro.serve.traffic import make_trace
    from repro.telemetry import Ledger

    n = args.requests if args.requests is not None else \
        (64 if args.executed else 100_000)
    kind = args.trace or "bursty"
    devices = args.dp * args.tp
    len_kw = dict(prompt_len_range=(4, min(48, args.max_len - 1)),
                  new_tokens_range=(4, args.new_tokens),
                  deadline_ms=args.deadline_ms, seed=args.seed)

    if args.colocated:
        pre_sc = dec_sc = baseline_config(
            args.arch, devices, slots=args.slots,
            max_len=args.max_len, page_size=args.page_size)
        print(f"# baseline (colocated single engine): {dec_sc.name}")
    else:
        # probe trace: the pool planner needs length statistics only
        probe = make_trace(kind, n=min(n, 2000), rate_rps=10.0,
                           **len_kw)
        table = None
        if args.route_table:
            try:
                table = load_route_table(args.route_table)
            except ValueError as exc:
                print(f"# ignoring route table: {exc}")
        pre_sc, dec_sc, notes = plan_pools(
            args.arch, devices, calib, probe, slo_ms=args.slo,
            slots=args.slots, max_len=args.max_len,
            page_size=args.page_size, route_table=table)
        print(f"# pool plan ({notes['source']}, "
              f"calibration: {calib.source}):")
        print(f"#   prefill -> {pre_sc.name} "
              f"({notes['prefill']['j_per_prompt']:.3e} J/prompt)")
        print(f"#   decode  -> {dec_sc.name} "
              f"({notes['decode']['j_per_token']:.3e} J/token)")

    rate = args.rate if args.rate is not None else \
        auto_rate_rps(dec_sc, calib, (4 + args.new_tokens) / 2,
                      replicas=args.decode_replicas)
    trace = make_trace(kind, n=n, rate_rps=rate, **len_kw)
    print(f"# trace: {kind} n={n} rate={rate:.2f} rps "
          f"slo={args.slo:.0f}ms "
          f"mode={'executed' if args.executed else 'modeled'}")

    ledger = Ledger(run="launch.serve.fleet",
                    jsonl_path=args.ledger or None,
                    meta={"arch": args.arch, "trace": kind,
                          "requests": n},
                    report_path=args.report_out or None)
    fc = FleetConfig(prefill=pre_sc, decode=dec_sc, slo_ms=args.slo,
                     executed=args.executed, colocated=args.colocated,
                     prefill_replicas=args.prefill_replicas,
                     decode_replicas=args.decode_replicas)
    router = FleetRouter(fc, calib=calib, ledger=ledger,
                         seed=args.seed)
    report = router.run(trace, sampling=sampling)
    ledger.close()

    _print_slo(report["slo"])
    pools = report["pools"]
    print(f"scale events: {report['scale_ups']} up / "
          f"{report['scale_downs']} down "
          f"(decode peak {pools['decode']['replicas_peak']} replicas)")
    for ev in report["scale_events"]:
        print(f"  t={ev['t_s']:8.2f}s {ev['pool']:7s} {ev['action']:4s} "
              f"-> {ev['replicas']} ({ev['reason']})")
    jt = report["j_per_token"]
    print(f"joules/token: prefill={jt['prefill']:.3e} "
          f"decode={jt['decode']:.3e} transfer={jt['transfer']:.3e}")
    print(f"joules/token [fleet]: {jt['fleet']:.3e}")
    xfer = report["transfer"]
    print(f"kv transfer: {xfer['measured']['migrations']:.0f} "
          f"migrations, "
          f"{xfer['measured']['transfer_wire_bytes']:.3e} bytes, "
          f"measured/predicted wire ratio = "
          f"{xfer['ratio_wire_bytes']:.4f}")
    if args.report_out:
        print(f"# wrote {len(ledger)} ledger rows -> {args.report_out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
