"""Predict-fn builders: adapt the repo's models to ``ServeEngine``'s
per-chain forward contract ``(single-chain params, queries (Q, ...)) ->
predictions (Q, ...)``.

Each builder closes over the model/config and returns a pure function the
engine vmaps over the chain axis, so Bayesian model averaging and credible
intervals come from the same forward passes training used — the transformer
builder goes through ``Model.prefill``, the entry point of the decode/serve
path, not a parallel reimplementation.
"""

from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models.mlp import apply_mlp

PyTree = Any
PredictFn = Callable[[PyTree, Any], jnp.ndarray]

# How far two programs computing the same bf16 model may disagree on a BMA
# log-probability: sharded against single-device, cached decode against a
# prefill forward, fused kernel against unfused ops.  The programs order and
# fuse the bf16 ops differently, so an activation can round the other way,
# and the head emits its logits in bf16: a logit then lands on a
# neighbouring bf16 value, a whole ulp away.  The models checked here keep
# |logit| below LOGIT_BOUND (an rms-normed hidden state times a head of std
# 1/sqrt(d_model)), where one bf16 ulp is 2^-5; LOGP_ATOL allows three such
# ulps.  Seen: up to 0.023 on the reduced qwen3 on CPU, 0.056 at qwen3-4b
# widths on a TPU v5e against a highest-precision forward.  A real fault
# moves them further: leaving the new token out of the paged kernel's
# attention moves them by 0.3 to 2.7 on the reduced qwen3.
LOGIT_BOUND = 8.0
LOGP_ATOL = 3 * 2.0**-5


def bma_logits(per_chain_logits: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    """Bayesian-model-averaged next-token log-probabilities.

    Reduces per-chain logits ``(C, ..., V)`` to the log of the *mean* of the
    per-chain softmax distributions — the posterior-predictive token law of
    the chain bank — computed stably in log space.  The single source of
    truth for the decode-time reduction: the sharded
    :class:`~repro.cluster.decode.DecodeEngine` path calls it on the
    all-gathered logit block, the single-device path on the vmapped output.
    """
    C = per_chain_logits.shape[axis]
    logp = jax.nn.log_softmax(per_chain_logits.astype(jnp.float32), axis=-1)
    return jax.nn.logsumexp(logp, axis=axis) - jnp.float32(math.log(C))


def regression_predict(reg) -> PredictFn:
    """Posterior-predictive of :class:`~repro.core.potentials.PolyRegression`:
    queries are raw inputs ``z (Q,)``, predictions ``phi(z)·w + b (Q,)``."""

    def predict(w, z):
        return reg.predict(w, reg.features(z))

    return predict


def mlp_predict(cfg) -> PredictFn:
    """Feed-forward block as a regression head: queries ``x (Q, d_model)``,
    predictions ``(Q, d_model)`` through :func:`~repro.models.mlp.apply_mlp`."""

    def predict(params, x):
        return apply_mlp(params, x, cfg)

    return predict


def transformer_next_token_predict(model) -> PredictFn:
    """Next-token logits through the serving path: queries are a prompt batch
    (``{"tokens": (Q, T)}``), predictions the last-position logits ``(Q, V)``
    from :meth:`~repro.models.transformer.Model.prefill` — ensemble-averaging
    them is Bayesian model averaging over the chain bank at decode time."""

    def predict(params, batch):
        logits, _ = model.prefill(params, batch)  # (Q, 1, V)
        return logits[:, 0].astype(jnp.float32)

    return predict
