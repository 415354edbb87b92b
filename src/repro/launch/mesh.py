"""Production meshes. Defined as FUNCTIONS so importing this module never
touches jax device state (device count is locked on first jax init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod adds the cross-DCN 'pod' axis
    (2 pods = 512 chips). Axes: data = DP/FSDP, model = TP/EP."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many host devices exist (tests/benchmarks)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_data_mesh(shards: int, axis: str = "data"):
    """1-D mesh over the reservoir co-partitioning axis: what the sharded
    manage loop and the D-R-TBS/D-T-TBS shard_map wrappers run on (the axis
    name must match :data:`repro.core.distributed.AXIS`)."""
    return jax.make_mesh((shards,), (axis,), axis_types=(AxisType.Auto,))
