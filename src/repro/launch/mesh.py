"""Device meshes for the sharded paths.

Functions, not module-level constants: importing this module never touches
jax device state.  Tests build the small meshes on forced host devices
(``--xla_force_host_platform_device_count``, set in a subprocess before jax
is imported); on a TPU host they span the real chips.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices):
    """A mesh whose axes GSPMD partitions (``AxisType.Auto``): the
    repository places arrays with ``NamedSharding`` and lets the compiler
    propagate the rest, which ``jax.make_mesh``'s default explicit axes
    refuse (a gather from a vocabulary-sharded embedding, a scan over
    sharded inputs)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for CI-scale sharding tests (8 forced host devices)."""
    n = data * model
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return _auto_mesh((data, model), ("data", "model"), devices[:n])


def make_data_mesh(n: int):
    """1-D ``data`` mesh over the first ``n`` devices: the layout
    :class:`~repro.cluster.ClusterEngine` and the bank engines shard their
    chain axis over."""
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return _auto_mesh((n,), ("data",), devices[:n])

