"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state.  The dry-run entry point (dryrun.py) force-creates 512
host-platform placeholder devices *before* importing anything else.

Target hardware: TPU v5e, 16x16 = 256 chips per pod; 2 pods = 512 chips.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices):
    """A mesh whose axes GSPMD partitions (``AxisType.Auto``): the
    repository places arrays with ``NamedSharding`` and lets the compiler
    propagate the rest, which ``jax.make_mesh``'s default explicit axes
    refuse (a gather from a vocabulary-sharded embedding, a scan over
    sharded inputs)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — run under dryrun.py "
            f"(it sets xla_force_host_platform_device_count)")
    return _auto_mesh(shape, axes, devices[:n])


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


def batch_axes_for(mesh, global_batch: int):
    """Which mesh axes shard the batch: all 'data-like' axes whose product
    divides the batch (long_500k's B=1 falls back to replication)."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if axes and global_batch % size == 0:
        return axes
    return ()


def fsdp_axes_for(mesh):
    """Axes used for the 2-D (fsdp_tp) parameter sharding."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
