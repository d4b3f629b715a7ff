#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Prints the comparison's numbers, each beside its limit, as the last
lines on standard error, and one JSON result as the last line on
standard output.  Exits 2, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for, or where the program is missing.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="PATH",
                    help="with --trace 1, also write the profiler's "
                         "trace to PATH, cut to what the reduction reads, "
                         "and the map of its op names to parts of the "
                         "step beside it (PATH's .xplane.pb -> "
                         ".scopes.json)")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return fail(f"no program under {src}: run from a checkout")
    sys.path[:0] = [ROOT, src]

    from bench import spec
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        return fail(str(e))

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < cell.chips:
        return fail(f"{cell.name} needs {cell.chips} chips; found "
                    f"{len(devices)}")
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    kind = devices[0].device_kind
    if kind not in peaks:
        return fail(f"no peaks for device kind {kind!r} in peaks.json")

    from bench import harness
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         peak=peaks[kind], t_process=T_PROCESS,
                         keep_trace=args.keep_trace)
    sys.stderr.flush()
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
