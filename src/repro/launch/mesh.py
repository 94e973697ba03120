"""Production meshes. Functions only — importing never touches jax devices.

Every mesh has ``Auto`` axes: the sharding rules and the model code leave
layout to the partitioner (``P.UNCONSTRAINED``, sharding constraints),
which ``jax.make_mesh``'s default ``Explicit`` axes reject.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    return make_mesh((data, model), ("data", "model"))


def make_serving_mesh(n_shards: int):
    """1-D ('data',) mesh over the first ``n_shards`` devices.

    The slot-sharded continuous engine's mesh (DESIGN.md §10): weights
    replicate, the slot axis shards.  Built from a device PREFIX (not
    ``jax.make_mesh``, which wants them all) so a 4-device container can
    host a 2-shard engine and a 4-shard engine in the same process —
    what the sharded serving bench sweeps.
    """
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < n_shards:
        raise ValueError(f"need {n_shards} devices for {n_shards} shards, "
                         f"have {len(devices)} (set "
                         f"--xla_force_host_platform_device_count on CPU)")
    return Mesh(np.array(devices[:n_shards]), ("data",))
