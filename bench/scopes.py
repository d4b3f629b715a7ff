#!/usr/bin/env python3
"""Device time per part of the train step: forward, backward, optimizer.

The program runs each differentiated loss body under
``jax.named_scope("model")`` and each optimizer update under
``jax.named_scope("optimizer")`` (``src/repro/obs/scopes.py``).  The
names reach only the compiled step's HLO metadata: an instruction's
``op_name`` reads ``.../jvp(model)/...`` in the forward,
``.../transpose(jvp(model))/...`` in the backward (recomputed forward
operations under remat included) and ``.../optimizer/...`` in the
update; a fused op may carry several, joined by ``;``.  The profiler
names each device op after its HLO instruction (``fusion.20``), so a
map from instruction name to class (``classes``) classes every
innermost-op interval that ``devtrace`` computes.

The map's source is the program's own compiled step,
``Compiled.as_text()``: the harness frees its step before it reduces the
trace, so ``program_scopes`` compiles it again as the harness does (the
compiler names the instructions of one program alike on every build,
and a step that differs only in metadata alike too), and the harness
hands the map to ``devtrace.Reduced``.  ``--keep-trace`` writes the map
beside the trace it keeps (``map_path``), and ``map`` writes one for a
trace kept another way; ``reduce`` reduces a kept trace again offline::

  python3 bench/scopes.py map --workload <cell> --out <trace>.scopes.json
  python3 bench/scopes.py reduce --workload <cell> --trace <trace> \\
      --map <trace>.scopes.json

A program that names no scope (one older than the scopes) reads None.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

# The program's scope names: a contract with src/repro/obs/scopes.py,
# spelled out here so that the benchmark runs on a program without them
MODEL, OPTIMIZER = "model", "optimizer"
CLASSES = ("forward", "backward", "optimizer", "unscoped")
SCOPED = CLASSES[:3]
_FORWARD = f"jvp({MODEL})"
_BACKWARD = f"transpose({_FORWARD})"
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([^\s=]+)\s*=(.*)$")
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?\bop_name="((?:[^"\\]|\\.)*)"')
_REFERENCE = re.compile(r"%([^\s,(){}=]+)")


def _parts(op_name: str) -> set:
    out = set()
    for part in op_name.split(";"):
        if _BACKWARD in part:
            out.add("backward")
        elif _FORWARD in part:
            out.add("forward")
        elif OPTIMIZER in part.split("/"):
            out.add("optimizer")
        else:
            out.add("unscoped")
    return out


def classify(op_name: str) -> str:
    """forward | backward | optimizer | unscoped, from an instruction's
    ``op_name``; a ``;``-joined name whose parts disagree is unscoped."""
    parts = _parts(op_name) if op_name else {"unscoped"}
    return parts.pop() if len(parts) == 1 else "unscoped"


def classes(hlo_text: str) -> dict:
    """Instruction name -> class, for each instruction of a compiled
    module's text.

    An instruction whose ``op_name`` names no scope, or that has none,
    takes the class of the instructions that use its value (a
    computation's root: of those that call the computation); where
    they differ, the earliest part of the step (forward, backward,
    optimizer).  The compiler makes such instructions: on a TPU the
    weights' bf16 cast and the gradient's zero fill, hoisted out of the
    layer loops with no ``op_name``; prefetch copies; the partitioner's
    broadcasts.  One that nothing scoped uses (the loss's all-reduce)
    stays unscoped, as does a ``;``-joined name whose parts disagree."""
    instrs, comp = [], None         # (name, class or None, refs, root of)
    for line in hlo_text.splitlines():
        if not line[:1].isspace():
            h = _HEADER.match(line)
            comp = h.group(1) if h else comp
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        root, name, rest = m.groups()
        op = _OP_NAME.search(rest)
        own = None
        if op:
            parts = _parts(op.group(1))
            if len(parts) > 1:
                own = "unscoped"
            elif parts != {"unscoped"}:
                own = parts.pop()
        instrs.append((name, own, _REFERENCE.findall(rest),
                       comp if root else None))
    users = collections.defaultdict(list)   # of a value or a computation
    for name, _, refs, _ in instrs:
        for r in refs:
            users[r].append(name)
    out = {}
    # operands and called computations come before their users in the
    # text, so one pass from the end meets every user's class first
    for name, own, _, root_of in reversed(instrs):
        if own is None:
            got = {out.get(u) for u in users[name] + users[root_of]}
            own = next((c for c in SCOPED if c in got), "unscoped")
        out[name] = own
    return out


def names_a_scope(scopes) -> bool:
    """Whether a map classes some instruction as a part of the step."""
    return bool(scopes) and bool(set(scopes.values()) & set(SCOPED))


def scope_s(r, scopes: dict):
    """Device seconds in the traced window per class, mean over chips,
    from ``r``'s innermost-op pieces (``devtrace.Reduced.ops``); None
    where the map classes no instruction as a scope."""
    if not names_a_scope(scopes):
        return None
    chips = len(r.ops)
    out = collections.Counter({c: 0.0 for c in CLASSES})
    for ops in r.ops:
        for s, e, name, _ in ops:
            out[scopes.get(name, "unscoped")] += (e - s) * 1e-9 / chips
    return out


def program_scopes(cell) -> dict:
    """The map of a cell's compiled step, built by its program on this
    machine's chips as the harness builds it, past the persistent
    compile cache: the cache's key leaves out HLO metadata, so a cached
    step may carry the ``op_name``s of another program with the same
    arithmetic (one older than the scopes names none)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.mesh import make_local_mesh

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        prog = cell.program.build(cell.config, cell.traffic,
                                  make_local_mesh(1, cell.chips))
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    return classes(prog.compiled.as_text())


def map_path(trace: str) -> str:
    """Where the map of a trace kept at ``trace`` goes:
    ``<name>.xplane.pb`` -> ``<name>.scopes.json``."""
    base = trace[:-len(".xplane.pb")] if trace.endswith(".xplane.pb") \
        else trace
    return base + ".scopes.json"


def write_map(path: str, cell, scopes: dict):
    import jax

    with open(path, "w") as f:
        json.dump({"workload": cell.name,
                   "device": jax.devices()[0].device_kind,
                   "scopes": scopes}, f, indent=0, sort_keys=True)


def read(r, cls: str):
    """A reader's number: device time per step in one class, ms, by the
    map that ``r`` keeps."""
    s = scope_s(r, r.scopes)
    return None if s is None else r.per_step_ms(s[cls])


def _kept(cell, trace: str, map_file: str, peak: dict) -> dict:
    from bench import devtrace

    with open(map_file) as f:
        scopes = json.load(f)["scopes"]
    r = devtrace.Reduced(devtrace.load(trace, cell.chips), cell, peak=peak,
                         scopes=scopes)
    s = scope_s(r, r.scopes) or {}
    return {"steps": r.steps, "busy_ms": r.per_step_ms(r.busy_s),
            "scope_ms": {c: r.per_step_ms(v) for c, v in s.items()},
            "breakdown": r.breakdown(top=20)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    m = sub.add_parser("map", help="write the map of a cell's compiled "
                       "step, built on this machine's chips")
    m.add_argument("--workload", required=True)
    m.add_argument("--out", required=True)
    r = sub.add_parser("reduce", help="a kept trace's device time per "
                       "step by class, and its top ops")
    r.add_argument("--workload", required=True)
    r.add_argument("--trace", required=True)
    r.add_argument("--map", required=True)
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from bench import spec

    cell = spec.load_cell(args.workload)
    if args.cmd == "map":
        write_map(args.out, cell, program_scopes(cell))
        return 0
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        peak = next(iter(json.load(f).values()))
    print(json.dumps(_kept(cell, args.trace, args.map, peak), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
