"""The gradient oracle the SGLD samplers consume.

``make_grad_fn`` builds ``grad_fn(params, batch) -> (grads, metrics)``:
value_and_grad of the model loss, with optional gradient accumulation over
microbatches (lax.scan) so the big shapes fit HBM.  Hand it to a
``repro.samplers`` preset with ``has_aux=True``, for instance
``samplers.sgld(mode, make_grad_fn(model), has_aux=True, ...)``, and drive
the sampler with :class:`repro.train.engine.Engine` or
:class:`repro.cluster.ClusterEngine`.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.transformer import Model, loss_fn
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

