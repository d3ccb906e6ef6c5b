"""One-line presets: the sampler zoo as transform chains.

    sampler = samplers.sgld("consistent", grad_fn, gamma=1e-2, sigma=0.5, tau=4)

is exactly

    Sampler(chain(delay_read(TraceDelay(tau)),
                  gradients(grad_fn),
                  langevin_noise(sigma),
                  apply_sgld_update()),
            gamma=gamma)

That chain is the one SGLD step every driver builds (the launcher, the
examples, the benchmark), run by :class:`~repro.train.engine.Engine` or
:class:`~repro.cluster.ClusterEngine`.  The zoo variants reuse the same
skeleton: :func:`svrg` swaps the gradient stage for the control-variate
:func:`~repro.samplers.transforms.svrg_gradients` oracle, :func:`sghmc`
swaps the commit pair for the momentum
:func:`~repro.samplers.transforms.sghmc_update`, and every preset takes
``stale_strength`` / ``stale_gamma_scale`` to splice the Chen-et-al.
:func:`~repro.samplers.transforms.stale_correction` in after the gradient
stage.  The equation-to-transform map lives in ``docs/THEORY.md``.
"""

from __future__ import annotations

from typing import Any, Callable

import jax.numpy as jnp

from repro.samplers.base import Sampler
from repro.samplers.policies import DelayPolicy, PerCoordinateDelay, TraceDelay
from repro.samplers.transform import SamplerTransform, chain
from repro.samplers.transforms import (
    GradFn,
    apply_sgld_update,
    batch_scaled_gamma,
    delay_read,
    fused_update,
    gradients,
    langevin_noise,
    masked_gradients,
    pipeline_overlap,
    sghmc_update,
    stale_correction,
    svrg_gradients,
)

MODES = ("sync", "consistent", "inconsistent", "pipeline")


def _front_parts(mode: str, *, tau: int, delay_policy: DelayPolicy | None,
                 fused: bool) -> list[SamplerTransform]:
    """The read-model head shared by every preset: validates ``mode`` /
    ``tau`` and returns the (possibly empty) ``delay_read`` stage."""
    if mode not in MODES:
        raise ValueError(f"unknown sampler mode {mode!r}")
    if mode in ("consistent", "inconsistent") and delay_policy is None \
            and tau < 1:
        raise ValueError(f"mode {mode!r} needs tau >= 1")
    parts: list[SamplerTransform] = []
    if mode in ("consistent", "inconsistent"):
        if delay_policy is None:
            delay_policy = (PerCoordinateDelay(tau, fused=fused)
                            if mode == "inconsistent" else TraceDelay(tau))
        parts.append(delay_read(delay_policy))
    return parts


def _stale_parts(stale_strength: float | None,
                 stale_gamma_scale: float) -> list[SamplerTransform]:
    """The optional Chen-et-al. correction stage (after the gradients)."""
    if stale_strength is None and stale_gamma_scale == 0.0:
        return []
    return [stale_correction(strength=(stale_strength or 0.0),
                             gamma_scale=stale_gamma_scale)]


def sgld(mode: str, grad_fn: GradFn, *, gamma=1e-2, sigma: float = 1.0,
         tau: int = 0, has_aux: bool = False, delay_policy: DelayPolicy | None = None,
         fused: bool = False,
         noise_dtype=jnp.float32, base_batch: int | None = None,
         stale_strength: float | None = None,
         stale_gamma_scale: float = 0.0) -> Sampler:
    """The paper's SGLD in any of its four read models.

    - ``sync``         X_hat = X_k (barrier baseline; tau = 0).
    - ``consistent``   X_hat = X_{k - tau_k} whole-vector stale read (W-Con).
    - ``inconsistent`` [X_hat]_i = [X_{s_i}]_i per-coordinate read (W-Icon).
    - ``pipeline``     previous step's gradient (tau = 1 W-Con on gradients)
                       whose all-reduce overlaps the next step's compute.

    ``fused=True`` commits through the Pallas fused kernel (noise generated
    in VMEM; compiled on a TPU, interpreted elsewhere);
    ``delay_policy`` overrides the mode's default policy.

    ``base_batch`` switches the chain to the heterogeneous-minibatch
    contract: ``grad_fn(params, example)`` becomes a *per-example* oracle
    evaluated through :func:`~repro.samplers.transforms.masked_gradients`
    over the executor's bucket-padded :class:`MaskedBatch` views, and the
    step size is linearly rescaled by ``size / base_batch``
    (:func:`~repro.samplers.transforms.batch_scaled_gamma`).

    ``stale_strength`` / ``stale_gamma_scale`` splice the Chen-et-al.
    :func:`~repro.samplers.transforms.stale_correction` in after the
    gradient stage (a bitwise no-op on commits with staleness 0).
    """
    parts = _front_parts(mode, tau=tau, delay_policy=delay_policy,
                         fused=fused)
    if base_batch is None:
        parts.append(gradients(grad_fn, has_aux=has_aux))
    else:
        parts.append(batch_scaled_gamma(base_batch))
        parts.append(masked_gradients(grad_fn, has_aux=has_aux))
    parts.extend(_stale_parts(stale_strength, stale_gamma_scale))
    if mode == "pipeline":
        parts.append(pipeline_overlap())
    if fused:
        parts.append(fused_update(sigma))
    else:
        parts.append(langevin_noise(sigma, noise_dtype=noise_dtype))
        parts.append(apply_sgld_update())
    return Sampler(transform=chain(*parts), gamma=gamma)


def svrg(mode: str, grad_fn: GradFn, full_grad_fn: Callable[[Any], Any], *,
         anchor_every: int = 64, gamma=1e-2, sigma: float = 1.0,
         tau: int = 0, has_aux: bool = False,
         delay_policy: DelayPolicy | None = None,
         noise_dtype=jnp.float32, base_batch: int | None = None,
         stale_strength: float | None = None,
         stale_gamma_scale: float = 0.0) -> Sampler:
    """SVRG-Langevin under any read model: :func:`sgld` with the gradient
    stage swapped for :func:`~repro.samplers.transforms.svrg_gradients`.

    ``full_grad_fn(params)`` evaluates the full-data gradient at the anchor
    (refreshed every ``anchor_every`` commits inside the scanned carry);
    ``grad_fn`` keeps the surrounding batch contract — a minibatch oracle by
    default, a *per-example* oracle under ``base_batch`` (the masked
    heterogeneous path, with the same linear ``gamma ∝ b`` scaling as
    :func:`sgld`).  ``stale_strength`` / ``stale_gamma_scale`` compose the
    Chen-et-al. correction after the variance-reduced oracle.
    """
    parts = _front_parts(mode, tau=tau, delay_policy=delay_policy,
                         fused=False)
    if base_batch is not None:
        parts.append(batch_scaled_gamma(base_batch))
    parts.append(svrg_gradients(grad_fn, full_grad_fn,
                                anchor_every=anchor_every, has_aux=has_aux))
    parts.extend(_stale_parts(stale_strength, stale_gamma_scale))
    if mode == "pipeline":
        parts.append(pipeline_overlap())
    parts.append(langevin_noise(sigma, noise_dtype=noise_dtype))
    parts.append(apply_sgld_update())
    return Sampler(transform=chain(*parts), gamma=gamma)


def sghmc(mode: str, grad_fn: GradFn, *, gamma=1e-2, sigma: float = 1.0,
          friction: float = 1.0, precond: Any = None, tau: int = 0,
          has_aux: bool = False, delay_policy: DelayPolicy | None = None,
          noise_dtype=jnp.float32, base_batch: int | None = None,
          stale_strength: float | None = None,
          stale_gamma_scale: float = 0.0) -> Sampler:
    """Stochastic-gradient HMC under any read model: :func:`sgld` with the
    ``langevin_noise + apply_sgld_update`` pair swapped for the momentum
    commit :func:`~repro.samplers.transforms.sghmc_update`.

    ``friction`` is the underdamped drag ``a`` and ``precond`` an optional
    diagonal inverse-mass preconditioner (scalar or params-shaped pytree) —
    the momentum/preconditioned variant motivated by the faster
    non-log-concave SGLD-family rates of Zou, Xu & Gu.  The momentum buffer
    lives in the sampler state (scanned carry), so it survives chunking and
    checkpoint round-trips.  All the delayed-read, masked-batch, and
    stale-correction machinery composes exactly as in :func:`sgld`.
    """
    parts = _front_parts(mode, tau=tau, delay_policy=delay_policy,
                         fused=False)
    if base_batch is None:
        parts.append(gradients(grad_fn, has_aux=has_aux))
    else:
        parts.append(batch_scaled_gamma(base_batch))
        parts.append(masked_gradients(grad_fn, has_aux=has_aux))
    parts.extend(_stale_parts(stale_strength, stale_gamma_scale))
    if mode == "pipeline":
        parts.append(pipeline_overlap())
    parts.append(sghmc_update(sigma, friction=friction, precond=precond,
                              noise_dtype=noise_dtype))
    return Sampler(transform=chain(*parts), gamma=gamma)

