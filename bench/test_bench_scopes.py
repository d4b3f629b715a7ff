"""Device time by program scope: the classes of op names, the map from a
compiled module, the readers on hand-made events and on a recorded
trace, and the program's compiled steps, which must name every
operation that does work (on the CPU, at a tiny size)."""
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from bench import devtrace, scopes, spec
from bench.conftest import tiny

READERS = ("forward_ms", "backward_ms", "optimizer_ms")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# A 0.05 s window of phantom16k-p4-b256 on a TPU v5e 2x2 from the scoped
# program, cut by ``bench/run.py --keep-trace``, and the map that
# ``bench/scopes.py map`` wrote on the same machine
SCOPED = os.path.join(os.path.dirname(__file__), "testdata",
                      "phantom16k-p4-b256-scoped")
# what the readers read from it, pinned
SCOPED_METRICS = {"forward_ms": 0.4073558850800192,
                  "backward_ms": 0.6177153452800072,
                  "optimizer_ms": 1.4270115765800264}


def _reduce(events, cell="phantom16k-p4-b256", scope_map=None):
    """The reduction with a map, as the harness makes it (none: an empty
    one, as a program that names no scope gives)."""
    return devtrace.Reduced(events, spec.load_cell(cell), peak=PEAK,
                            scopes=scope_map or {})


def _read(r):
    return {m: spec.load_metric(m).read(r) for m in READERS}


@pytest.mark.parametrize("op_name,cls", [
    ("jit(step_fn)/jvp(model)/while/body/dot_general", "forward"),
    ("jit(step_fn)/shard_map/jvp(model)/while/body/all_gather", "forward"),
    ("jit(step_fn)/transpose(jvp(model))/while/body/dot_general",
     "backward"),
    ("jit(step_fn)/transpose(jvp(model))/while/body/closed_call/"
     "checkpoint/rematted_computation/mul", "backward"),
    ("jit(step_fn)/optimizer/mul", "optimizer"),
    ("jit(step_fn)/psum", "unscoped"),
    ("jit(step_fn)/optimizer_state/mul", "unscoped"),
    ("jit(step_fn)/jvp()/while/body/dot_general", "unscoped"),
    ("", "unscoped"),
    ("a/transpose(jvp(model))/mul;a/transpose(jvp(model))/broadcast_in_dim",
     "backward"),
    ("a/jvp(model)/mul;a/jvp(model)/while/body/add", "forward"),
    ("a/transpose(jvp(model))/mul;a/optimizer/mul", "unscoped"),
    ("a/jvp(model)/mul;a/psum", "unscoped"),
])
def test_classify(op_name, cls):
    assert scopes.classify(op_name) == cls


MODULE = """\
HloModule jit_step_fn, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%body (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]) parameter(0)
  %mul.1 = f32[4]{0} multiply(%gte.1, %gte.1), metadata={op_name="jit(step_fn)/transpose(jvp(model))/while/body/mul"}
  ROOT %tuple.1 = (s32[], f32[4]) tuple(%gte.0, %mul.1)
}

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %convert.21 = bf16[4]{0} convert(%p)
  %fusion.1 = f32[4]{0} fusion(%convert.21), kind=kLoop, calls=%fused, metadata={op_name="jit(step_fn)/jvp(model)/while/body/max"}
  %broadcast.2 = f32[4]{0} broadcast(%c), dimensions={}
  %tuple.2 = (s32[], f32[4]) tuple(%c, %broadcast.2)
  %while.5 = (s32[], f32[4]) while(%tuple.2), condition=%cond, body=%body, metadata={op_name="jit(step_fn)/transpose(jvp(model))/while"}
  %gte.5 = f32[4]{0} get-tuple-element(%while.5), index=1
  %copy.3 = f32[4]{0} copy(%convert.21)
  %fusion.20 = f32[4]{0} fusion(%gte.5, %copy.3), kind=kLoop, calls=%adam, metadata={op_name="jit(step_fn)/optimizer/mul;jit(step_fn)/optimizer/sub"}
  %fusion.7 = f32[4]{0} fusion(%fusion.20, %fusion.1), kind=kLoop, calls=%f7, metadata={op_name="jit(step_fn)/optimizer/add;jit(step_fn)/jvp(model)/add"}
  %all-reduce = f32[] all-reduce(%fusion.1), to_apply=%sum, metadata={op_name="jit(step_fn)/psum"}
  %index = s32[] fusion(%c), kind=kLoop, calls=%f8, metadata={op_name="jit(step_fn)/shard_map/jit(_take)/select_n"}
  ROOT %out = (f32[4], f32[]) tuple(%fusion.20, %all-reduce, %index)
}
"""


def test_classes_of_a_module():
    """Named instructions class by name; the compiler's unnamed ones by
    the instructions that use them, the earliest part of the step first;
    a loop body's root by the loop; an op that nothing scoped uses, or a
    fused name whose parts disagree, stays unscoped."""
    c = scopes.classes(MODULE)
    assert c["fusion.1"] == "forward"
    assert c["mul.1"] == c["while.5"] == "backward"
    assert c["tuple.1"] == "backward"             # the body's root
    assert c["broadcast.2"] == c["tuple.2"] == "backward"
    assert c["fusion.20"] == c["copy.3"] == "optimizer"
    assert c["convert.21"] == "forward"           # forward and optimizer
    assert c["fusion.7"] == "unscoped"            # parts disagree
    assert c["all-reduce"] == c["index"] == c["out"] == "unscoped"


def _events(names, **kw):
    # one chip, window 0..100 ns, 2 steps; the ops run back to back
    ops, t = [], 0
    for name, dur in names:
        ops.append([t, dur, name, "loop fusion"])
        t += dur
    return dict({"window": [0, 100], "steps": 2, "devices": [ops],
                 "host": []}, **kw)


HAND_MADE = [("fusion.1", 20), ("fusion.2", 30), ("fusion.3", 25),
             ("copy.1", 5), ("copy.9", 10)]
HAND_MAP = {"fusion.1": "forward", "fusion.2": "backward",
            "fusion.3": "optimizer", "copy.1": "unscoped"}


def test_readers_on_hand_made_events():
    """Each class's innermost-op time per step; an op the map does not
    name is unscoped; the four classes sum to the busy time; each top
    op is named with its class."""
    r = _reduce(_events(HAND_MADE), scope_map=HAND_MAP)
    assert _read(r) == pytest.approx({"forward_ms": 10e-6,
                                      "backward_ms": 15e-6,
                                      "optimizer_ms": 12.5e-6})
    s = scopes.scope_s(r, r.scopes)
    assert s["unscoped"] == pytest.approx(15e-9)
    assert sum(s.values()) == pytest.approx(r.busy_s)
    ops = dict(r.breakdown()["device_ops"])
    assert ops == pytest.approx({"forward:fusion.1": 20e-9,
                                 "backward:fusion.2": 30e-9,
                                 "optimizer:fusion.3": 25e-9,
                                 "unscoped:copy.1": 5e-9,
                                 "unscoped:copy.9": 10e-9})


def test_a_loop_and_several_chips():
    """A loop keeps what its body leaves; two chips average."""
    ev = {"window": [0, 100], "steps": 1, "host": [],
          "devices": [[[0, 60, "while.4", "while"],
                       [10, 20, "fusion.1", "convolution fusion"],
                       [60, 20, "fusion.20", "loop fusion"]],
                      [[0, 40, "fusion.20", "loop fusion"]]]}
    r = _reduce(ev, "dense16k-b256", {"while.4": "forward",
                                      "fusion.1": "backward",
                                      "fusion.20": "optimizer"})
    assert _read(r) == pytest.approx({"forward_ms": 20e-6,
                                      "backward_ms": 10e-6,
                                      "optimizer_ms": 30e-6})


@pytest.mark.parametrize("kw", [{}, {"scope_map": {}},
                                {"scope_map": {"fusion.1": "unscoped"}}])
def test_no_map_reads_none(kw):
    """Without a map that names a scope (a program older than the
    scopes), every reader reads None and op names stay as they are."""
    r = _reduce(_events(HAND_MADE), **kw)
    assert _read(r) == dict.fromkeys(READERS)
    assert [n for n, _ in r.breakdown()["device_ops"]] == [
        n for n, _ in sorted(HAND_MADE, key=lambda o: -o[1])]


def test_scope_names_match_the_program():
    from repro.obs import scopes as program
    assert (scopes.MODEL, scopes.OPTIMIZER) == (program.MODEL,
                                                program.OPTIMIZER)


# instructions that do work on a device, by opcode
WORK = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(?:\(.*?\)|\S+)\s+"
                  r"(dot|convolution|fusion|custom-call|all-gather|"
                  r"all-reduce|reduce-scatter|all-to-all|"
                  r"collective-permute)(?:-start|-done)?\(")
# The loss's sum over chips runs after the backward and feeds only the
# step's output: it is the one op allowed outside every scope
LOSS_SUM = re.compile(r'op_name="[^"]*/psum"')


def _unscoped_work(text):
    c = scopes.classes(text)
    return [line.strip()[:160] for line in text.splitlines()
            if (m := WORK.match(line)) and c[m.group(1)] == "unscoped"
            and not (m.group(2) == "all-reduce" and LOSS_SUM.search(line))]


def _lm_step_text():
    from repro.configs.base import ShapeConfig, get_config
    from repro.launch.mesh import make_local_mesh
    from repro.launch.specs import input_specs
    from repro.optim import make_optimizer
    from repro.parallel.axes import MeshAxes
    from repro.parallel.params import abstract
    from repro.train.trainer import make_train_step

    mesh = make_local_mesh(1, 4)
    cfg = get_config("stablelm-3b", smoke=True)
    batch, bspec = input_specs(cfg, ShapeConfig("s", 64, 8, "train"),
                               MeshAxes.from_mesh(mesh))
    step, decls, opt_decls = make_train_step(
        cfg, mesh, make_optimizer("adamw", 1e-3), batch_spec=bspec)
    return step.lower(abstract(decls), abstract(opt_decls),
                      jax.ShapeDtypeStruct((), jnp.int32),
                      batch).compile().as_text()


def _ffn_step_text(name, chips):
    cell = tiny(name, chips)
    from repro.launch.mesh import make_local_mesh
    prog = cell.program.build(cell.config, cell.traffic,
                              make_local_mesh(1, chips))
    return prog.compiled.as_text()


@pytest.mark.parametrize("step", ["dense tp=1", "phantom tp=4", "lm"])
def test_program_steps_name_every_op(step):
    """Every dot, convolution, fusion, custom call and collective of the
    compiled step classes as forward, backward or optimizer, but the
    loss's sum; each class is present."""
    text = (_lm_step_text() if step == "lm" else
            _ffn_step_text("dense16k-b256", 1) if step == "dense tp=1"
            else _ffn_step_text("phantom16k-p4-b256", 4))
    assert _unscoped_work(text) == []
    assert set(scopes.SCOPED) <= set(scopes.classes(text).values())


def test_readers_build_the_program_map():
    """The map that the harness builds from the program's step again
    after the window classes the step's op names for the readers."""
    cell = tiny("dense16k-b256", 1)
    first = {}
    for name, cls in scopes.program_scopes(cell).items():
        first.setdefault(cls, name)
    ops = [(first["forward"], 20), (first["backward"], 30),
           (first["optimizer"], 40)]
    r = devtrace.Reduced(_events(ops), cell, peak=PEAK,
                         scopes=scopes.program_scopes(cell))
    assert _read(r) == pytest.approx({"forward_ms": 10e-6,
                                      "backward_ms": 15e-6,
                                      "optimizer_ms": 20e-6})


@pytest.fixture
def compile_cache(tmp_path):
    """JAX's persistent compile cache on, in ``tmp_path``, for one test."""
    from jax.experimental.compilation_cache import compilation_cache

    want = {"jax_enable_compilation_cache": True,
            "jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0,
            "jax_persistent_cache_min_entry_size_bytes": 0}
    saved = {k: getattr(jax.config, k) for k in want}
    for k, v in want.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    try:
        yield tmp_path
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_map_is_built_past_the_compile_cache(compile_cache, monkeypatch):
    """A cache that holds the same step compiled without the scope names,
    as a program older than them leaves it (the cache's key leaves out
    metadata), does not hide the scopes from the map."""
    from repro.launch.mesh import make_local_mesh
    from repro.obs import scopes as program

    cell = tiny("dense16k-b256", 1)
    with monkeypatch.context() as m:
        m.setattr(program, "MODEL", "unnamed")
        m.setattr(program, "OPTIMIZER", "unnamed_update")
        older = cell.program.build(cell.config, cell.traffic,
                                   make_local_mesh(1, 1))
    assert set(scopes.classes(older.compiled.as_text()).values()) == {
        "unscoped"}
    assert os.listdir(compile_cache)
    assert set(scopes.SCOPED) <= set(scopes.program_scopes(cell).values())


def test_recorded_scoped_phantom_trace():
    """The three scopes of a recorded trace of the scoped program, to
    the digit; together they cover at least 98% of the busy time."""
    cell = spec.load_cell("phantom16k-p4-b256")
    with open(scopes.map_path(SCOPED + ".xplane.pb")) as f:
        scope_map = json.load(f)["scopes"]
    r = _reduce(devtrace.load(SCOPED + ".xplane.pb", cell.chips),
                scope_map=scope_map)
    assert _read(r) == SCOPED_METRICS
    assert sum(SCOPED_METRICS.values()) >= 0.98 * r.per_step_ms(r.busy_s)
