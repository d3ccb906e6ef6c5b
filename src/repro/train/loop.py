"""Training-loop substrate: microbatched gradients + async-SGLD samplers.

``make_grad_fn`` builds the gradient oracle the SGLD sampler consumes:
value_and_grad of the model loss, with optional gradient accumulation over
microbatches (lax.scan) so the big shapes fit HBM.  ``make_train_step``
wires it into a ``repro.samplers`` preset (any mode: sync / consistent /
inconsistent / pipeline), and ``train_loop`` drives it through the unified
scan-chunked :class:`repro.train.engine.Engine`.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro import samplers
from repro.core.sgld import SGLDConfig
from repro.models.transformer import Model, loss_fn
from repro.train.engine import Engine, log_hook
from repro.utils import tree_add_scaled, tree_zeros_like

PyTree = Any


def _split_microbatch(batch: PyTree, n: int) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)


def make_grad_fn(model: Model, num_microbatches: int = 1):
    """grad_fn(params, batch) -> (grads, metrics) for the SGLD sampler."""

    def single(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, batch), has_aux=True)(params)
        metrics = dict(metrics, loss=loss)
        return grads, metrics

    if num_microbatches <= 1:
        return single

    def accumulated(params, batch):
        micro = _split_microbatch(batch, num_microbatches)

        def add(a, b):  # float metrics are means, counts are sums
            if jnp.issubdtype(a.dtype, jnp.integer):
                return a + b
            return a + b / num_microbatches

        def body(carry, mb):
            g_acc, m_acc = carry
            g, m = single(params, mb)
            g_acc = tree_add_scaled(g_acc, g, 1.0 / num_microbatches)
            m_acc = jax.tree_util.tree_map(add, m_acc, m)
            return (g_acc, m_acc), None

        g0 = tree_zeros_like(params)
        m0 = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(single, params,
                           jax.tree_util.tree_map(lambda x: x[0], micro))[1])
        (grads, metrics), _ = jax.lax.scan(body, (g0, m0), micro)
        return grads, metrics

    return accumulated


def make_train_step(model: Model, sgld_cfg: SGLDConfig, num_microbatches: int = 1,
                    *, fused: bool = False):
    """Returns (sampler, step_fn); step_fn(state, batch, delay) -> (state, metrics)."""
    grad_fn = make_grad_fn(model, num_microbatches)
    sampler = samplers.from_config(sgld_cfg, grad_fn, has_aux=True,
                                   fused=fused)

    def step_fn(state, batch, delay=0):
        return sampler.step(state, batch, delay)

    return sampler, step_fn


def train_loop(model: Model, params: PyTree, sgld_cfg: SGLDConfig,
               batch_fn: Callable[[jax.Array], PyTree], steps: int,
               key: jax.Array, delays=None, log_every: int = 10,
               log_fn=print, num_microbatches: int = 1, chunk_size: int = 0):
    """Train through the unified Engine: one jitted scan per chunk, delays as
    device arrays (no per-delay-value retraces), logging via hook.

    Returns ``(state, history)`` with history = [(step, loss), ...] at the
    ``log_every`` cadence, as the old per-step loop did.
    """
    sampler, _ = make_train_step(model, sgld_cfg, num_microbatches)
    key, init_key = jax.random.split(key)
    state = sampler.init(params, init_key)
    engine = Engine(sampler, batch_fn=batch_fn,
                    chunk_size=chunk_size or max(1, log_every),
                    hooks=[log_hook(every=log_every, log_fn=log_fn)])
    state, aux = engine.run(state, steps=steps, delays=delays, key=key)
    losses = aux["loss"]
    idx = sorted(set(range(0, steps, log_every)) | {steps - 1})
    history = [(k, float(losses[k])) for k in idx]
    return state, history
