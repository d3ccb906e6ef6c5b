"""Pallas TPU kernels for the hot paths: the fused Langevin update, the
W-Icon stale read, and the contiguous and paged decode steps.

Every kernel is staged through :func:`on_backend`, which picks its mode
from the platform the program is lowered for: compiled by Mosaic for a
TPU, run by the Pallas interpreter on every other platform.  No caller
chooses, so nothing on a TPU runs a kernel interpreted, and a program
compiled for a described TPU (``jax.experimental.topologies``) from a CPU
host gets the compiled kernels too.
"""

from __future__ import annotations

import functools

import jax


def on_backend(call, *args):
    """``call(*args, interpret=...)`` with ``interpret`` fixed when the
    program is lowered: ``False`` for a TPU, ``True`` elsewhere.  Both
    branches are traced; the compiler only ever sees the chosen one."""
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(call, interpret=False),
        default=functools.partial(call, interpret=True))
