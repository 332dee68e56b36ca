"""Mesh construction helper shared by every sharded entry point.

``make_mesh`` is the one-liner the launch CLIs, the sharded fit and
the sharded predict share: a 1-axis ``Mesh`` over all local devices,
named per the repo's mesh/axis convention (docs/architecture.md — the
data-parallel axis is called ``"data"`` unless a caller says otherwise).
``shard_map`` and ``axis_size`` come straight from ``jax`` /
``jax.lax``; the installed JAX is the floor.
"""
from __future__ import annotations

import jax
import numpy as np


def make_mesh(axis: str = "data", devices=None) -> "jax.sharding.Mesh":
    """1-axis device mesh over ``devices`` (default: all local devices).

    Parameters
    ----------
    axis : str
        Name of the single (data-parallel) mesh axis.
    devices : sequence of jax.Device or None
        Devices to place on the axis; None uses ``jax.devices()``.

    Returns
    -------
    jax.sharding.Mesh
        The mesh accepted by ``GEEK.fit(..., mesh=)``,
        ``core.distributed.make_predict_sharded``, and the ``mesh=``
        streaming path.
    """
    from jax.sharding import Mesh
    return Mesh(np.array(devices if devices is not None
                         else jax.devices()), (axis,))
