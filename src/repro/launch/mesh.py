"""Production mesh construction (function, not constant: importing this
module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes.  JAX 0.9 makes ``Explicit``
    axes by default, under which ``with_sharding_constraint`` rejects the
    logical-axis specs of :mod:`repro.distributed.sharding`."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh for tests (requires xla_force_host_platform_device_count
    to be set by the test before first jax use)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def make_lane_mesh(n_shards: int, axis: str = "data"):
    """1-D serving mesh over the first ``n_shards`` local devices — the
    axis the mux's lane dimension is sharded over (lanes are
    batch-parallel, so a flush's lane axis maps straight onto it).
    Raises when the host exposes fewer devices (on CPU, set
    ``--xla_force_host_platform_device_count`` first — see
    :mod:`repro.launch.xla_env`)."""
    import numpy as np

    devices = jax.devices()
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if n_shards > len(devices):
        raise ValueError(
            f"lane mesh needs {n_shards} devices; only {len(devices)} "
            "available (set --xla_force_host_platform_device_count)")
    return jax.sharding.Mesh(np.asarray(devices[:n_shards]), (axis,))
