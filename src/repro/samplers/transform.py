"""The composable sampler-transform protocol: optax-style ``(init, update)``.

A :class:`SamplerTransform` is a pure pair of functions threaded by the
:class:`~repro.samplers.base.Sampler` driver:

- ``init(params) -> state`` builds the transform's own state pytree
  (a ring buffer of iterates, a pending gradient, or ``()``).
- ``update(ctx, state) -> (ctx, state)`` reads and rewrites fields of the
  per-step :class:`StepContext` — the read point ``x_hat``, the gradient,
  the Langevin noise, or the committed ``params`` — and advances its state.

``chain(*transforms)`` composes transforms left-to-right into one
transform whose state is the tuple of member states, exactly like
``optax.chain``.  The paper's four read models are one-line chains over
five primitives (see :mod:`repro.samplers.presets`).

Each primitive carries a stage ``name`` (its factory's name), and ``chain``
runs a named member's ``update`` under ``jax.named_scope(name)``: the
compiled program's op metadata then reads ``.../delay_read/...``,
``.../gradients/...``, ``.../fused_update/...``, so a profile attributes
device time to stages by name.  A scope is trace-time metadata only.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax

PyTree = Any


class StepContext(NamedTuple):
    """Everything one SGLD commit can read or rewrite.

    Built fresh by the driver each step; transforms communicate through it
    instead of through positional plumbing (the old ``delay_k`` argument).
    """

    params: PyTree               # current iterate X_k (rewritten by apply stages)
    x_hat: PyTree                # gradient read point (rewritten by delay_read)
    grads: Optional[PyTree]      # set by the gradients stage
    noise: Optional[PyTree]      # set by langevin_noise
    aux: Any                     # metrics surfaced by the gradients stage
    gamma: jax.Array             # step size gamma_k (schedule-evaluated)
    key_noise: jax.Array         # per-step PRNG key for Langevin noise
    key_delay: jax.Array         # per-step PRNG key for coordinate delays
    step: jax.Array              # int32 commit counter k
    delay: jax.Array             # int32 realized staleness tau_k for this commit
    batch: Any                   # opaque payload handed to the gradient oracle


InitFn = Callable[[PyTree], Any]
UpdateFn = Callable[[StepContext, Any], tuple[StepContext, Any]]


class SamplerTransform(NamedTuple):
    """An optax-style (init, update) pair over :class:`StepContext`, with
    the stage name :func:`chain` scopes its update under (``None``: no
    scope of its own)."""

    init: InitFn
    update: UpdateFn
    name: Optional[str] = None


def stateless(update_ctx: Callable[[StepContext], StepContext],
              name: Optional[str] = None) -> SamplerTransform:
    """Lift a pure ``ctx -> ctx`` function into a stateless transform
    named ``name``."""

    def init(params):
        del params
        return ()

    def update(ctx, state):
        return update_ctx(ctx), state

    return SamplerTransform(init, update, name)


def chain(*transforms: SamplerTransform) -> SamplerTransform:
    """Compose transforms left-to-right; state is the tuple of member states.
    A named member's update runs under ``jax.named_scope`` of its name."""

    def init(params):
        return tuple(t.init(params) for t in transforms)

    def update(ctx, state):
        new_state = []
        for t, s in zip(transforms, state):
            if t.name is None:
                ctx, s = t.update(ctx, s)
            else:
                with jax.named_scope(t.name):
                    ctx, s = t.update(ctx, s)
            new_state.append(s)
        return ctx, tuple(new_state)

    return SamplerTransform(init, update)
