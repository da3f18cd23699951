"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  The dry-run launcher sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import.

Expert parallelism (DESIGN.md §10) adds an optional ``"expert"`` mesh axis,
carved out of the data dimension: the same devices that were pure data
replicas additionally own a slice of the expert axis, and the MoE layer's
shard_map all-to-all runs over that axis while FSDP/batch sharding keeps
using the remaining "data"/"pod" axes.

Every axis is ``AxisType.Auto``: the sharding rules place parameters and
batches with PartitionSpecs and let GSPMD propagate the rest, which is what
``jax.make_mesh``'s default of ``Explicit`` axes would refuse.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (see module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False, expert: int = 1):
    data = 16
    if expert > 1:
        if data % expert != 0:
            raise ValueError(
                f"expert-parallel size {expert} must divide the data axis "
                f"({data}) it is carved from")
        shape = (data // expert, expert, 16)
        axes = ("data", "expert", "model")
        if multi_pod:
            shape, axes = (2,) + shape, ("pod",) + axes
        return make_mesh(shape, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1, expert: int = 0):
    """Small mesh over however many devices exist (tests).  ``expert > 0``
    appends an "expert" axis of that size (an explicit size-1 axis is valid:
    the EP dispatch path runs unchanged with a single expert shard)."""
    if expert > 0:
        return make_mesh((data, expert, model), ("data", "expert", "model"))
    return make_mesh((data, model), ("data", "model"))
