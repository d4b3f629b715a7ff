"""One run of one cell: set-up, the measured window, the comparison.

Set-up builds the program's compiled train step once, feeds it the
seed's weights, and drives it through the compared first steps and a
short warm-up on the window's own loop, which times a step.  The window
then runs that same object for ``seconds``: ``AHEAD_SECONDS`` of steps
in flight, the host time at which each step's loss is ready recorded,
no step waited on twice; when the time is up nothing more is sent, and
the window closes once every step sent has finished.
After the window the program's state is freed and the plain reference
follows the first steps on one chip.  Everything that belongs to one
model, its step, inputs, reference and operation count, comes from the
cell's program module (``spec.py`` lists what the harness calls).
"""
from __future__ import annotations

import collections
import gc
import math
import shutil
import sys
import tempfile
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench import compare, data, devtrace, scopes

TRACE_SECONDS = 2.0     # longest traced window
WARM_SECONDS = 0.25     # warm-up after the compared steps, two in flight
# Device work kept in flight in the window, so that a stall of the host
# shorter than this leaves the chip busy: at most a quarter of the window
AHEAD_SECONDS = 4.0
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts backend compiles while it is open."""

    def __init__(self):
        self.count = 0

    def _seen(self, event, duration_secs, **kw):
        if event == BACKEND_COMPILE:
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._seen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._seen)


def run_loop(prog, state, pool, step, seconds, ahead=2):
    """Dispatch steps, keeping ``ahead`` of them in flight, until
    ``seconds`` have passed since the start; then dispatch nothing more
    and wait for every step sent.  Returns the state, the start time,
    each step's completion time as the host saw it, each step's loss (on
    the device) and the next step index."""
    params, opt_state = state
    inflight = collections.deque()
    done, losses = [], []

    def wait():
        with TraceAnnotation("bench.wait"):
            losses.append(inflight.popleft().block_until_ready())
        done.append(time.perf_counter())

    t_start = time.perf_counter()
    with TraceAnnotation("bench.window"):
        while True:
            with TraceAnnotation("bench.select"):
                batch = pool[step % len(pool)]
            with TraceAnnotation("bench.dispatch"):
                params, opt_state, loss = prog(params, opt_state, step,
                                               *batch)
            inflight.append(loss)
            step += 1
            if len(inflight) >= ahead:
                wait()
            if time.perf_counter() - t_start >= seconds:
                break
        while inflight:
            wait()
    return (params, opt_state), t_start, done, losses, step


def p95(values):
    """Nearest-rank 95th percentile."""
    s = sorted(values)
    return s[max(math.ceil(0.95 * len(s)) - 1, 0)]


def _memory_peak(devices):
    stats = [d.memory_stats() or {} for d in devices]
    return max(s.get("peak_bytes_in_use", 0) for s in stats)


def build(cell):
    """The program's compiled step for a cell, its tp and its chips."""
    from repro.launch.mesh import make_local_mesh

    tp = cell.program.mesh_tp(cell.config, cell.chips)
    mesh = make_local_mesh(1, cell.chips)
    prog = cell.program.build(cell.config, cell.traffic, mesh)
    return prog, tp, list(mesh.devices.flat)


def first_steps(cell, prog, tp, seed):
    """Feed the program the seed's weights and batch pool, and drive it
    through the compared first steps on the window's own call and feed.
    Returns the state, the pool, and the numbers the comparison reads."""
    cfg, program = cell.config, cell.program
    key = data.root_key(seed)
    params = jax.jit(lambda k: program.init_params(cfg, tp, k),
                     out_shardings=prog.param_sharding)(key)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), prog.abstract_params)
    if got != want:
        raise ValueError(f"the program's parameters are {want}, the "
                         f"benchmark makes {got}")
    opt_state = prog.init_opt(params)
    pool = jax.jit(lambda k: program.batch_pool(cfg, cell.traffic, k))(key)
    pool = [tuple(b) for b in jax.device_put(pool, prog.batch_sharding)]

    norms = jax.jit(lambda t: program.leaf_norms(cfg, tp, t))
    change = jax.jit(lambda t, k: program.change_norms(cfg, tp, t, k))
    losses = []
    for s in range(program.FIRST_STEPS):
        params, opt_state, loss = prog(params, opt_state, s, *pool[s])
        losses.append(loss)
        if s == 0:
            grad_norms = norms(program.grad_tree(opt_state))
    numbers = {"losses": [float(a) for a in losses],
               "grad_norms": [float(a) / (1 - prog.b1) for a in grad_norms],
               "change_norms": [float(a) for a in
                                change(program.param_tree(params), key)]}
    return (params, opt_state), pool, numbers


def reference_steps(cell, tp, seed, device, rows=None, **kw):
    """The reference's numbers on one chip, from the seed's weights and
    the same batches, or their first ``rows`` rows (the program's
    ``first_steps`` takes ``kw``)."""
    cfg, program = cell.config, cell.program
    with jax.default_device(device):
        pool = jax.jit(lambda k: program.batch_pool(cfg, cell.traffic, k))(
            data.root_key(seed))
        batches = [tuple(a[:rows] for a in b)
                   for b in pool[:program.FIRST_STEPS]]
        del pool
        return program.first_steps(cfg, tp, seed, batches, **kw)


def run(cell, seed: int, seconds: float, trace: bool, *, peak: dict,
        t_process: float, keep_trace: str = None):
    """Run one cell; returns the result line's dict."""
    cfg, batch = cell.config, cell.traffic["global_batch"]
    marks = [("start-up", time.perf_counter())]
    prog, tp, devices = build(cell)
    marks.append(("build", time.perf_counter()))
    state, pool, prog_first = first_steps(cell, prog, tp, seed)
    marks.append(("first steps", time.perf_counter()))
    state, t0, done, _, step = run_loop(prog, state, pool,
                                        cell.program.FIRST_STEPS,
                                        WARM_SECONDS)
    marks.append(("warm-up", time.perf_counter()))
    print("set-up: " + ", ".join(
        f"{name} {t - t_prev:.2f} s" for (name, t), t_prev in
        zip(marks, [t_process] + [t for _, t in marks[:-1]])),
        file=sys.stderr)

    window = min(seconds, TRACE_SECONDS) if trace else seconds
    step_s = (done[-1] - t0) / len(done)
    ahead = max(2, math.ceil(min(AHEAD_SECONDS, window / 4) / step_s))
    print(f"steps in flight: {ahead} ({step_s * 1e3:.3f} ms a step in "
          "the warm-up)", file=sys.stderr)
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    with CompileCounter() as compiles:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        t_setup = time.perf_counter() - t_process
        try:
            state, t0, done, wlosses, step = run_loop(
                prog, state, pool, step, window, ahead)
        finally:
            if trace:
                jax.profiler.stop_trace()
    wl = np.asarray(jax.device_get(wlosses))
    mem_peak = _memory_peak(devices)
    del state, pool, prog, wlosses
    gc.collect()

    steps = len(done)
    result = {"correct": False, "attempted": steps,
              "failed": int(np.sum(~np.isfinite(wl))), "metrics": {},
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": int(mem_peak)}}
    if trace:
        try:
            events = devtrace.load(tdir, len(devices))
            if keep_trace:
                devtrace.trim(tdir, keep_trace)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        scope_map = scopes.program_scopes(cell)
        if keep_trace:
            scopes.write_map(scopes.map_path(keep_trace), cell, scope_map)
        red = devtrace.Reduced(events, cell, peak=peak, scopes=scope_map)
        result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        for entry, mod in cell.per_layer:
            v = mod.read(red)
            if v is not None:
                result["metrics"][entry["name"]] = {"value": v,
                                                    "unit": entry["unit"]}
        result["breakdown"] = red.breakdown()
    else:
        span = done[-1] - t0
        durs = np.diff([t0] + done)
        step_flops = cell.program.step_flops(cfg, tp, cell.traffic)
        e2e = {"samples_per_s": steps * batch / span,
               "step_ms_p95": 1e3 * p95(durs),
               "mfu": 100.0 * step_flops * steps / span
               / peak["bf16_flops_per_s"],
               "setup_s": t_setup}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}

    t_ref = time.perf_counter()
    ref = reference_steps(cell, tp, seed, devices[0])
    print(f"reference: {time.perf_counter() - t_ref:.2f} s",
          file=sys.stderr)
    nums = compare.numbers(prog_first, ref)
    compared = {k: {"value": nums[k], "limit": cell.limits[k]}
                for k in compare.NUMBERS}
    compared["failed_steps"] = {"value": result["failed"], "limit": 0}
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in compared.values())
    result["compiles_in_window"] = compiles.count
    print(f"compiles in the window: {compiles.count}", file=sys.stderr)
    for k, c in compared.items():
        print(f"compared {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
        if not math.isfinite(c["value"]):
            c["value"] = None           # JSON has no inf; it failed above
    result["compared"] = compared
    return result
