"""Device mesh construction helpers."""

from __future__ import annotations

import jax
from jax.sharding import Mesh

SERIES_AXIS = "series"  # data-parallel axis: series blocks across chips
TIME_AXIS = "time"      # sequence-parallel axis: contiguous time tiles
EXPERT_AXIS = "expert"  # expert axis: aggregator families across chips
HOST_AXIS = "host"      # multi-host axis: collectives here cross DCN


def make_mesh(n_devices: int | None = None,
              axis: str = SERIES_AXIS, devices=None) -> Mesh:
    """A 1-D mesh over the first n devices (default: all).

    Series sharding is the primary axis (the DP analog): every chip owns a
    block of series and all of their points, so downsample and per-series
    math need no communication; only the cross-series group stage reduces
    over the mesh. Pass ``devices`` explicitly to mesh a non-default
    platform (e.g. ``jax.devices("cpu")`` for the virtual test mesh).
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, have {len(devices)}")
        devices = devices[:n_devices]
    return Mesh(devices, (axis,))
