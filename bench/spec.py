"""Finds a cell's files by the names BENCHMARK.json gives, and checks them.

A later cell, configuration, traffic mix or per-layer metric is added
as files and entries only; nothing here names one of them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

from bench import compare

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIR = "bench"

# end-to-end metrics, computed by the harness from the host clock
END_TO_END = ("samples_per_s", "step_ms_p95", "mfu", "setup_s")
METRIC_KEYS = ("UNIT", "LAYER", "MOVES")


class SpecError(ValueError):
    """A file the benchmark needs is missing or disagrees."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
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


def load_metric(name: str, root: str = ROOT):
    """The reader of one per-layer metric, ``bench/metrics/<name>.py``."""
    path = os.path.join(root, DIR, "metrics", name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no reader {path} for metric {name}")
    mod_name = "bench_metric_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} has no read()")
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
    return Cell(name, w["chips"], cfg, traffic, limits, e2e, per_layer)


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
