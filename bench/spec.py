"""Finds a cell's files by the names BENCHMARK.json gives, and checks them.

A later cell, configuration, traffic mix, per-layer metric or program is
added as files and entries only; nothing here names one of them.

A configuration file names its program (``"program": "<name>"``), the
module ``programs/<name>.py``, through which the harness reaches the
system under test, its inputs, its plain reference and its operation
count; it calls nothing else of it.  The module provides:

  FIRST_STEPS                  steps the comparison follows
  mesh_tp(cfg, chips)          the tensor-parallel ranks of the cell's mesh
  build(cfg, traffic, mesh)    the compiled train step: an object with
                               ``compiled``, ``param_sharding``,
                               ``opt_sharding``, ``batch_sharding`` (each
                               batch array's), ``abstract_params``,
                               ``init_opt(params)``, ``b1`` (the first
                               moment's decay) and ``__call__(params,
                               opt_state, step, *batch) -> (params,
                               opt_state, loss)``
  init_params(cfg, tp, key)    the seed's weights, under ``jit``
  batch_pool(cfg, traffic, key)  the seed's batches, a list of tuples of
                               arrays, under ``jit`` on one device
  grad_tree(opt_state)         the first moment, (1 - b1) x the first
                               gradient after one step
  param_tree(params)           the tree whose leaves the comparison reads
  first_steps(cfg, tp, seed, batches, dtype=, precision=, **kw)
                               the plain reference's numbers for
                               ``compare.numbers``; ``dtype=bfloat16,
                               precision="default"`` is the control
  leaf_norms(cfg, tp, tree), change_norms(cfg, tp, tree, key)
                               per-leaf norms, in the reference's order
  step_flops(cfg, tp, traffic), matmul_floor_s(cfg, tp, traffic, peak)
                               required matmul FLOPs of a step on a chip,
                               and the least time a chip spends on them
  tiny_config(cfg)             the configuration at the CPU tests' size
  no_exchange(cfg, tp)         ``first_steps`` keywords that leave the
                               exchange between chips out (a fault for
                               ``calibrate.py``; multi-chip programs)
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

from bench import compare

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIR = "bench"

# end-to-end metrics, computed by the harness from the host clock
END_TO_END = ("samples_per_s", "step_ms_p95", "mfu", "setup_s")
METRIC_KEYS = ("UNIT", "LAYER", "MOVES")
PROGRAM_API = ("FIRST_STEPS", "mesh_tp", "build", "init_params",
               "batch_pool", "grad_tree", "param_tree", "first_steps",
               "leaf_norms", "change_norms", "step_flops", "matmul_floor_s",
               "tiny_config")


# a program's name, as BENCHMARK.json's names: no path, no leading dot
PROGRAM_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


class SpecError(ValueError):
    """A file the benchmark needs is missing or disagrees."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    program: object        # the module programs/<config's program>.py
    traffic: dict
    limits: dict
    end_to_end: list       # BENCHMARK.json entries this cell reports
    per_layer: list        # (entry, reader module) pairs


def _json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"{path}: {e}") from e


def load_benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _module(kind: str, name: str, root: str):
    """The module ``bench/<kind>/<name>.py``, loaded by file name."""
    path = os.path.join(root, DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no {path} for {kind} {name}")
    mod_name = f"bench_{kind}_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str, root: str = ROOT):
    """The reader of one per-layer metric, ``bench/metrics/<name>.py``."""
    mod = _module("metrics", name, root)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{mod.__file__} has no read()")
    return mod


def load_program(name: str, root: str = ROOT):
    """A configuration's program, ``bench/programs/<name>.py``."""
    mod = _module("programs", name, root)
    missing = [a for a in PROGRAM_API if not hasattr(mod, a)]
    if missing:
        raise SpecError(f"{mod.__file__} lacks {missing}")
    return mod


def _applies(entry: dict, cell: str, reported: set) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry["moves"] in reported


def load_cell(name: str, root: str = ROOT, bench: dict = None) -> Cell:
    bench = bench or load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = _json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _json(os.path.join(root, DIR, "traffic",
                                 w["traffic"] + ".json"))
    limits = _json(os.path.join(root, DIR, "workloads",
                                name + ".json"))["limits"]
    if cfg.get("name") != w["config"]:
        raise SpecError(f"{w['config']}'s file names {cfg.get('name')}")
    if not (isinstance(cfg.get("program"), str)
            and PROGRAM_NAME.fullmatch(cfg["program"])):
        raise SpecError(f"{w['config']}'s file names no program: "
                        f"{cfg.get('program')!r}")
    program = load_program(cfg["program"], root)
    if set(limits) != set(compare.NUMBERS):
        raise SpecError(f"{name}: limits {sorted(limits)}, want "
                        f"{sorted(compare.NUMBERS)}")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    for m in e2e:
        if m["name"] not in END_TO_END:
            raise SpecError(f"end-to-end metric {m['name']} is not one the "
                            f"harness takes ({END_TO_END})")
    reported = {m["name"] for m in e2e}
    per_layer = []
    for m in bench["per_layer"]:
        if not _applies(m, name, reported):
            continue
        mod = load_metric(m["name"], root)
        got = tuple(getattr(mod, k, None) for k in METRIC_KEYS)
        want = (m["unit"], m["layer"], m["moves"])
        if got != want:
            raise SpecError(f"metric {m['name']}: reader says {got}, "
                            f"BENCHMARK.json {want}")
        per_layer.append((m, mod))
    return Cell(name, w["chips"], cfg, program, traffic, limits, e2e,
                per_layer)


def validate(root: str = ROOT) -> list:
    """Load every cell of BENCHMARK.json; raises SpecError on a fault."""
    bench = load_benchmark(root)
    cells = [load_cell(w["name"], root, bench) for w in bench["workloads"]]
    names = {c.name for c in cells}
    for m in bench["per_layer"] + bench["end_to_end"]:
        unknown = set(m.get("workloads", ())) - names
        if unknown:
            raise SpecError(f"metric {m['name']} lists unknown cells "
                            f"{sorted(unknown)}")
    return cells
