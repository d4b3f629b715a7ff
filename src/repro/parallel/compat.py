"""The one ``shard_map`` call site, plus the comm/compute overlap recipe.

Every shard_map in the repo goes through ``shard_map`` here (the static
audit's lint rule enforces it), so a future move of the jax API touches
one file.
"""
from __future__ import annotations

import os

import jax

# Async-collective + latency-hiding-scheduler recipe (SNIPPETS.md snippet
# 2): lets XLA start the k-wide ghost all-gather early and overlap it with
# the local diagonal GEMM instead of serializing gather -> decompress.
# These are scheduling hints only — the lowered HLO still contains the
# same collectives, so the PR-6 audit's pricing is unchanged.
COMM_OVERLAP_FLAGS = {
    "gpu": ("--xla_gpu_enable_async_collectives=true "
            "--xla_gpu_enable_latency_hiding_scheduler=true "
            "--xla_gpu_enable_highest_priority_async_stream=true"),
    "tpu": ("--xla_tpu_enable_async_collective_fusion=true "
            "--xla_tpu_enable_async_collective_fusion_fuse_all_gather"
            "=true "
            "--xla_tpu_overlap_compute_collective_tc=true "
            "--xla_enable_async_all_gather=true "
            "--xla_tpu_enable_latency_hiding_scheduler=true"),
    # CPU XLA has no async-collective scheduler and rejects the
    # accelerator-only flags, so overlap is a no-op there.
    "cpu": "",
}

# Where each platform reads its compiler flags.  The TPU flags belong to
# libtpu: XLA_FLAGS aborts on them ("Unknown flags in XLA_FLAGS"), while
# LIBTPU_INIT_ARGS accepts them and rejects a misspelt one.
COMM_OVERLAP_ENV = {"gpu": "XLA_FLAGS", "tpu": "LIBTPU_INIT_ARGS"}


def comm_overlap_flags(platform: str) -> str:
    """The flag fragment enabling comm/compute overlap on ``platform``
    ("tpu" | "gpu" | "cpu")."""
    try:
        return COMM_OVERLAP_FLAGS[platform]
    except KeyError:
        raise ValueError(f"unknown platform {platform!r}; known: "
                         f"{sorted(COMM_OVERLAP_FLAGS)}") from None


def enable_comm_overlap(platform: str) -> str:
    """Append the overlap recipe for ``platform`` to the environment
    variable that platform's compiler reads (``COMM_OVERLAP_ENV``).

    Must run before jax initializes its backend (both variables are read
    at client creation); idempotent — flags already present are not
    re-appended.  Returns the flags applied ("" on cpu)."""
    flags = comm_overlap_flags(platform)
    if not flags:
        return ""
    var = COMM_OVERLAP_ENV[platform]
    current = os.environ.get(var, "")
    missing = [f for f in flags.split() if f not in current]
    if missing:
        os.environ[var] = " ".join(([current] if current else []) + missing)
    return " ".join(missing)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
