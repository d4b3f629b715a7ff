"""Measured per-step costs from a compiled XLA executable.

This is the *measured* half of the energy ledger: where the analytic
model predicts flops and collective traffic from ``ProjectionStrategy``
objects, ``analyze_compiled`` reads what the compiler actually lowered —

  * ``cost_analysis()``   per-device FLOPs and HBM bytes accessed
  * ``memory_analysis()`` per-device buffer footprint (proves it fits)
  * the post-optimization HLO text, parsed for collective ops and
    converted to per-device wire bytes under the ring model
    (``launch/hlo_analysis.py``)

Caveat that the dry-run already documents: XLA counts each ``scan`` /
while-loop body ONCE, so for exact totals compile with layers unrolled
(``cfg.scan_layers=False``; the paper-FFN layer stack always runs
unrolled, and the pipelined FFN probe unrolls its tick loop too).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from repro.launch.hlo_analysis import collective_bytes, collective_m_floats

# HLO op name -> the paper's collective name (Eqn. 26 / Table III keys).
HLO_TO_PAPER = {
    "all-gather": "all_gather",
    "all-reduce": "all_reduce",
    "reduce-scatter": "reduce_scatter",
    "all-to-all": "all_to_all",
    "collective-permute": "collective_permute",
}


@dataclass
class CompiledCosts:
    """Per-device measured costs of one compiled step."""
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_wire_bytes: float = 0.0
    collective_m_floats: float = 0.0   # paper Eqn. 26 message units
    collectives: dict = field(default_factory=dict)  # per-HLO-op breakdown
    memory: dict = field(default_factory=dict)

    def as_dict(self) -> dict:
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "collective_wire_bytes_per_device": self.collective_wire_bytes,
            "collective_m_floats": self.collective_m_floats,
            "collectives": self.collectives,
            "memory": self.memory,
        }

    def measured_fields(self) -> dict:
        """The subset the ledger joins against predictions."""
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "collective_wire_bytes_per_device": self.collective_wire_bytes,
            "collective_m_floats": self.collective_m_floats,
        }


def _cost_dict(compiled) -> dict:
    ca = compiled.cost_analysis()
    if ca is None:
        return {}
    if isinstance(ca, (list, tuple)):     # older jax: one dict per device
        ca = ca[0] if ca else {}
    return dict(ca)


def _memory_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    return {
        "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
        "output_bytes": getattr(ma, "output_size_in_bytes", None),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
        "alias_bytes": getattr(ma, "alias_size_in_bytes", None),
        "code_bytes": getattr(ma, "generated_code_size_in_bytes", None),
    }


# One analysis per lowered module: every analysis call site (dry-run
# cells, planner HBM-fit checks, probes) funnels through these caches so
# a module is compiled and parsed at most once per process.  Keys hash
# the HLO text — the canonical identity of a lowered/compiled module —
# plus the collective group size the parse assumes.  Analysis results
# are small dicts; compiled executables pin device programs, so that
# cache is a bounded LRU (a long benchmark run compiling dozens of
# distinct modules must not retain them all).
_ANALYSIS_CACHE: dict = {}     # (hlo_hash, group) -> CompiledCosts
_COMPILE_CACHE: "OrderedDict" = None   # lowered_hlo_hash -> executable
_COMPILE_CACHE_MAX = 8


def _compile_cache():
    global _COMPILE_CACHE
    if _COMPILE_CACHE is None:
        from collections import OrderedDict
        _COMPILE_CACHE = OrderedDict()
    return _COMPILE_CACHE


def clear_analysis_cache():
    _ANALYSIS_CACHE.clear()
    _compile_cache().clear()


def analyze_compiled(compiled, default_group: int = 1) -> CompiledCosts:
    """Extract measured per-device costs from a ``lowered.compile()``
    result.  ``default_group`` is the collective group size assumed when
    an HLO op carries no ``replica_groups`` (normally the model-axis
    size).  Results are memoized on the optimized-HLO text, so repeated
    analysis of the same executable (dry-run + cost-fix + planner) pays
    for the parse once."""
    text = compiled.as_text()
    key = (hash(text), default_group)
    if key in _ANALYSIS_CACHE:
        return _ANALYSIS_CACHE[key]
    ca = _cost_dict(compiled)
    wire, breakdown = collective_bytes(text, default_group=default_group)
    costs = CompiledCosts(
        flops=float(ca.get("flops", 0.0)),
        hbm_bytes=float(ca.get("bytes accessed", 0.0)),
        collective_wire_bytes=float(wire),
        collective_m_floats=collective_m_floats(breakdown, default_group),
        collectives=breakdown,
        memory=_memory_dict(compiled),
    )
    _ANALYSIS_CACHE[key] = costs
    return costs


def compile_lowered(lowered):
    """LRU-cached ``lowered.compile()`` keyed on the lowered HLO text —
    call sites that re-lower an identical module (the planner checking
    HBM fit for a plan the dry-run already compiled, cost-fix reruns)
    skip the compile entirely."""
    cache = _compile_cache()
    lkey = hash(lowered.as_text())
    compiled = cache.get(lkey)
    if compiled is None:
        compiled = lowered.compile()
        cache[lkey] = compiled
        while len(cache) > _COMPILE_CACHE_MAX:
            cache.popitem(last=False)
    else:
        cache.move_to_end(lkey)
    return compiled


def analyze_lowered(lowered, default_group: int = 1,
                    keep_compiled: bool = False):
    """Compile (cached) + analyze a ``fn.lower(...)`` result."""
    compiled = compile_lowered(lowered)
    costs = analyze_compiled(compiled, default_group=default_group)
    if keep_compiled:
        return costs, compiled
    return costs


def analyze_lowerable(fn, *args, default_group: int = 1,
                      keep_compiled: bool = False):
    """Lower + compile ``fn(*args)`` (ShapeDtypeStructs are fine) and
    analyze it.  Returns ``CompiledCosts`` or, with ``keep_compiled``,
    ``(CompiledCosts, compiled)`` so callers can also execute it."""
    return analyze_lowered(fn.lower(*args), default_group=default_group,
                           keep_compiled=keep_compiled)
