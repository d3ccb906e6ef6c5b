"""Delay policies: how a commit chooses the stale read point ``X_hat_k``.

A :class:`DelayPolicy` decides the read from the realized staleness
``delay_k`` fed per step: ``delay_read(policy)`` owns the iterate ring
buffer and delegates the read to the policy.

- :class:`ConstantDelay` — worst-case fixed staleness ``tau`` (theory
  experiments), with the can't-be-staler-than-``k`` warm-up built in.
- :class:`TraceDelay` — consistent (W-Con, Assumption 2.1) whole-vector read
  at the realized staleness fed per step (e.g. from a
  :class:`~repro.core.delay_model.DelayTrace`).
- :class:`PerCoordinateDelay` — inconsistent (W-Icon, Assumption 2.3)
  per-coordinate read ``[X_hat]_i = [X_{s_i}]_i`` with
  ``s_i ~ U{0..tau_k}``; set ``fused=True`` to gather through the Pallas
  ``delay_gather`` kernel instead of the jnp reference path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Protocol, runtime_checkable

import jax.numpy as jnp

from repro.core.delay import (
    RingBuffer,
    read_consistent,
    read_inconsistent,
    sample_coordinate_delays,
)
from repro.kernels.ops import fused_delay_gather
from repro.samplers.transform import StepContext

PyTree = Any


@runtime_checkable
class DelayPolicy(Protocol):
    """Chooses the read point for one commit from the iterate history.

    ``tau`` is the static maximum staleness (ring depth is ``tau + 1``);
    ``read`` maps the per-step context + ring to the pytree ``X_hat_k``.
    """

    tau: int

    def read(self, ctx: StepContext, ring: RingBuffer) -> PyTree:
        ...


@dataclass(frozen=True)
class ConstantDelay:
    """W-Con read at fixed staleness ``tau`` (clamped to the commit count)."""

    tau: int

    def read(self, ctx: StepContext, ring: RingBuffer) -> PyTree:
        """Whole-vector read ``X_{k - min(k, tau)}`` from the ring."""
        return read_consistent(ring, jnp.minimum(ctx.step, self.tau))


@dataclass(frozen=True)
class TraceDelay:
    """W-Con read at the realized per-commit staleness ``ctx.delay``."""

    tau: int

    def read(self, ctx: StepContext, ring: RingBuffer) -> PyTree:
        """Whole-vector read ``X_{k - ctx.delay}`` from the ring."""
        return read_consistent(ring, ctx.delay)


@dataclass(frozen=True)
class PerCoordinateDelay:
    """W-Icon read: each coordinate from its own snapshot in ``[k-tau_k, k]``."""

    tau: int
    fused: bool = False

    def read(self, ctx: StepContext, ring: RingBuffer) -> PyTree:
        """Per-coordinate read: sample each coordinate's staleness in
        ``[0, ctx.delay]`` from ``ctx.key_delay`` and gather it from the
        ring (through the Pallas ``delay_gather`` kernel when ``fused``)."""
        delays = sample_coordinate_delays(ctx.key_delay, ring, ctx.delay)
        if self.fused:
            return fused_delay_gather(ring.history, delays, ring.head,
                                      ring.depth)
        return read_inconsistent(ring, delays)
