"""Production meshes.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; tests/benches use small local meshes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, pp: int = 1):
    """dp×tp (×pod) production mesh, optionally with a leading pipeline
    axis.  Pipeline stages are the OUTERMOST axis: stage-boundary traffic
    is the lowest-volume communication, so it gets the slowest links.
    Stages come out of the leading (pod/data) dimension, which pp must
    divide — silently shrinking a 256-chip pod would idle paid-for
    devices."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if pp > 1:
        if shape[0] % pp:
            raise ValueError(
                f"pp={pp} does not divide the leading "
                f"{axes[0]}={shape[0]} axis of the production mesh")
        shape = (pp, shape[0] // pp) + shape[1:]
        axes = ("pipe",) + axes
    return _make_mesh(shape, axes)


def make_local_mesh(dp: int = 2, tp: int = 4, pp: int = 1):
    """Small mesh over the first dp*tp*pp host devices (tests, benches,
    examples, the chip smoke run).

    ``pp > 1`` adds a leading ``pipe`` axis (pipeline stages); meshes
    without one behave exactly as before (pp=1).  A request larger than
    the host raises: shrinking it would run a different configuration
    (a 4-chip mesh on one chip) than the caller asked for.
    """
    n = len(jax.devices())
    pp = max(pp, 1)
    if dp * tp * pp > n:
        raise ValueError(f"mesh dp={dp} x tp={tp} x pp={pp} needs "
                         f"{dp * tp * pp} devices; host has {n}")
    if pp > 1:
        return _make_mesh((pp, dp, tp), ("pipe", "data", "model"))
    return _make_mesh((dp, tp), ("data", "model"))
