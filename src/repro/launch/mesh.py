"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (v5e-256).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis is pure
data parallelism across the slow inter-pod links (gradient all-reduce only,
optionally MX-compressed — see parallel/compression.py).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run pins the device count before any
mesh is built).

Every mesh in the repo is built by :func:`make_mesh`, with ``Auto`` axes:
the model code relies on GSPMD propagation (``with_sharding_constraint``
hints, gathers that XLA partitions), which ``jax.make_mesh``'s default
``Explicit`` axes refuse.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh",
           "mesh_from_flag"]


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-partitioned)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    return make_mesh((data, model), ("data", "model"))


def mesh_from_flag(flag: Optional[str]):
    """Parse the launcher's ``--mesh data,model[,pod]`` flag.

    "4,2" -> Mesh(data=4, model=2); "2,2,2" -> Mesh(pod=2, data=2, model=2)
    with "pod" outermost (slowest-varying device order, matching the
    physical slow inter-pod links).  Empty/None -> None (single device)."""
    if not flag:
        return None
    try:
        dims = tuple(int(x) for x in flag.split(","))
    except ValueError as e:
        raise ValueError(f"bad --mesh {flag!r}: {e}") from None
    if len(dims) == 2:
        return make_mesh(dims, ("data", "model"))
    if len(dims) == 3:
        data, model, pod = dims
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    raise ValueError(f"--mesh wants 2 or 3 comma-separated ints, got {flag!r}")
