"""Static sharding & energy audit CLI.

  PYTHONPATH=src python -m repro.launch.audit --all

Lowers every shipped jitted entrypoint (paper-FFN train probe, 1F1B
pipeline probe, serving prefill/decode) WITHOUT executing anything,
runs the ``repro.analysis`` rule engine over the optimized HLO /
jaxpr, lints the repo source, and writes ``AUDIT_report.json``
(schema ``audit-report/v1``).  Exit status 1 when any ERROR-severity
finding survives the checked-in suppression baseline
(``AUDIT_baseline.json``) — warnings and info report but don't gate.
See docs/analysis.md for the rule catalog.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_BASELINE = os.path.join(ROOT, "AUDIT_baseline.json")
DEFAULT_OUT = os.path.join(ROOT, "AUDIT_report.json")


def build_parser():
    ap = argparse.ArgumentParser(
        prog="repro.launch.audit",
        description="prove every lowered collective is priced before "
                    "anything runs")
    ap.add_argument("--all", action="store_true",
                    help="audit every shipped entrypoint family plus "
                         "the source lint (the CI job)")
    ap.add_argument("--unit", default="",
                    help="only units whose name contains this substring")
    ap.add_argument("--arch", default="qwen2.5-14b",
                    help="architecture for the serving units")
    ap.add_argument("--source-only", action="store_true",
                    help="AST lint only — no lowering (fast)")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual host devices for the lowering meshes")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="suppression baseline (missing file = empty)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="accept all current findings into the baseline "
                         "(deliberate ratchet reset)")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="report path (audit-report/v1)")
    return ap


def audit(args) -> int:
    from repro.analysis import load_baseline, run_audit
    from repro.analysis.findings import write_baseline

    units = []
    if not args.source_only:
        from repro.analysis.units import build_default_units
        units = build_default_units(arch=args.arch)
        if args.unit:
            units = [u for u in units if args.unit in u.name]
    baseline = load_baseline(args.baseline)
    result = run_audit(units, baseline=baseline, source_root=ROOT)

    if args.update_baseline:
        write_baseline(result.findings, args.baseline)
        print(f"# baseline: accepted {len(result.findings)} findings "
              f"into {args.baseline}")
        result = run_audit(units, baseline=load_baseline(args.baseline),
                           source_root=ROOT)

    result.write(args.out)
    print("\n".join(result.summary_lines()))
    print(f"# wrote {args.out}")
    return 0 if result.ok else 1


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if not (args.all or args.unit or args.source_only):
        build_parser().error("pick a scope: --all, --unit, or "
                             "--source-only")
    if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    return audit(args)


if __name__ == "__main__":
    sys.exit(main())
