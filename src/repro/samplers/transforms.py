"""The five sampler-transform primitives behind the paper's read models.

Raw leafwise math (``noise_like`` / ``sgld_apply``) lives here too: the
unfused noise and commit that :func:`langevin_noise` and
:func:`apply_sgld_update` apply, exported for code that needs the bare
arithmetic (the kernel microbenchmark).
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import delay as delay_lib
from repro.kernels.ops import fused_langevin_update
from repro.samplers.transform import SamplerTransform, StepContext, stateless
from repro.utils import tree_keys, tree_zeros_like

if TYPE_CHECKING:  # annotation-only; a runtime import would cycle via core
    from repro.samplers.policies import DelayPolicy

PyTree = Any
GradFn = Callable[..., PyTree]  # grad_fn(params, batch) -> grads | (grads, aux)


class MaskedBatch(NamedTuple):
    """A bucket-padded minibatch view: ``data`` leaves carry a leading
    bucket axis of ``B >= size`` examples, of which only the first ``size``
    are real.  The executor pads every commit's window up a shape-bucket
    ladder so a heterogeneous batch schedule compiles one trace per rung —
    the same discipline :class:`~repro.cluster.serve.ServeEngine` applies to
    query batches — and :func:`masked_gradients` averages over exactly the
    real examples, so padding rows never touch the math."""

    data: Any        # pytree; leaves (B, ...) bucket-padded examples
    size: jax.Array  # () int32 count of real examples (<= B)


def batch_mask(batch: MaskedBatch) -> jax.Array:
    """(B,) float32 indicator of the real examples in a padded view."""
    b = jax.tree_util.tree_leaves(batch.data)[0].shape[0]
    return (jnp.arange(b) < batch.size).astype(jnp.float32)


def masked_mean(values: PyTree, size: jax.Array) -> PyTree:
    """Mean of the first ``size`` rows of every ``(B, ...)`` leaf — the
    single reduction behind the masked gradient oracle (bitwise equal to
    ``jnp.mean`` when ``size == B``, since the mask multiplies by 1.0)."""

    def reduce(v):
        mask = (jnp.arange(v.shape[0]) < size).astype(v.dtype)
        mask = mask.reshape((-1,) + (1,) * (v.ndim - 1))
        return jnp.sum(v * mask, axis=0) / size.astype(v.dtype)

    return jax.tree_util.tree_map(reduce, values)


# ---------------------------------------------------------------------------
# raw leafwise math (the unfused noise and commit)
# ---------------------------------------------------------------------------
def noise_like(key: jax.Array, params: PyTree, scale: jnp.ndarray, dtype) -> PyTree:
    """sqrt(2 sigma gamma) * G_k, one independent key per leaf, shard-local."""
    keytree = tree_keys(key, params)
    return jax.tree_util.tree_map(
        lambda k, p: (scale * jax.random.normal(k, jnp.shape(p), dtype)).astype(p.dtype),
        keytree,
        params,
    )


def sgld_apply(params: PyTree, grads: PyTree, gamma: jnp.ndarray, noise: PyTree) -> PyTree:
    """x - gamma*g + noise, leafwise (the fused Pallas path is ``fused_update``)."""
    return jax.tree_util.tree_map(
        lambda p, g, n: (p - gamma.astype(p.dtype) * g.astype(p.dtype) + n).astype(p.dtype),
        params,
        grads,
        noise,
    )


def _key_bits(key: jax.Array) -> jax.Array:
    """(2,) uint32 view of a PRNG key (raw or typed) for the Pallas RNG."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return key.astype(jnp.uint32)


# ---------------------------------------------------------------------------
# transform primitives
# ---------------------------------------------------------------------------
def gradients(grad_fn: GradFn, has_aux: bool = False) -> SamplerTransform:
    """Evaluate the gradient oracle at the (possibly stale) read point."""

    def update(ctx: StepContext) -> StepContext:
        out = grad_fn(ctx.x_hat, ctx.batch)
        grads, aux = out if has_aux else (out, None)
        return ctx._replace(grads=grads, aux=aux)

    return stateless(update, "gradients")


def masked_gradients(grad_fn: GradFn, has_aux: bool = False) -> SamplerTransform:
    """Evaluate a *per-example* gradient oracle over a :class:`MaskedBatch`.

    ``grad_fn(params, example)`` is vmapped over the padded bucket axis and
    reduced with :func:`masked_mean`, so the committed gradient averages
    exactly the ``size`` real examples regardless of how far the bucket
    ladder padded the view — mixed batch sizes change the mask contents,
    never the trace.  With ``has_aux`` the per-example aux is masked-mean
    reduced the same way.
    """

    def update(ctx: StepContext) -> StepContext:
        mb = ctx.batch
        if not isinstance(mb, MaskedBatch):
            raise TypeError("masked_gradients needs a MaskedBatch (did you "
                            "mean gradients(), or forget batch_policy=?)")
        out = jax.vmap(lambda e: grad_fn(ctx.x_hat, e))(mb.data)
        per_grads, per_aux = out if has_aux else (out, None)
        grads = masked_mean(per_grads, mb.size)
        aux = masked_mean(per_aux, mb.size) if has_aux else None
        return ctx._replace(grads=grads, aux=aux)

    return stateless(update, "masked_gradients")


def batch_scaled_gamma(base_batch: int) -> SamplerTransform:
    """Linear step-size scaling for heterogeneous batches: a commit that
    averaged ``b`` examples advances the Langevin discretization with
    ``gamma_k * b / base_batch`` (and the injected noise, which reads
    ``ctx.gamma`` downstream, scales accordingly) — so one large-batch
    commit covers the same integrator time as ``b/base_batch`` base-size
    commits, at lower gradient variance.  A no-op scale of exactly 1.0 when
    ``b == base_batch``, keeping the fixed policy bit-compatible."""

    def update(ctx: StepContext) -> StepContext:
        mb = ctx.batch
        if not isinstance(mb, MaskedBatch):
            raise TypeError("batch_scaled_gamma needs a MaskedBatch upstream")
        scale = mb.size.astype(jnp.float32) / jnp.float32(base_batch)
        return ctx._replace(gamma=ctx.gamma * scale)

    return stateless(update, "batch_scaled_gamma")


def langevin_noise(sigma: float, schedule=None, noise_dtype=jnp.float32) -> SamplerTransform:
    """Draw the injected noise ``sqrt(2 sigma gamma_k) G_k`` into ``ctx.noise``.

    ``schedule`` optionally overrides the driver's ``gamma_k`` for the noise
    scale only (e.g. to anneal temperature independently of the step size).
    """

    def update(ctx: StepContext) -> StepContext:
        gamma = schedule(ctx.step) if schedule is not None else ctx.gamma
        scale = jnp.sqrt(2.0 * sigma * gamma)
        return ctx._replace(noise=noise_like(ctx.key_noise, ctx.params, scale,
                                             noise_dtype))

    return stateless(update, "langevin_noise")


def apply_sgld_update() -> SamplerTransform:
    """Commit ``X_{k+1} = X_k - gamma_k grad + noise`` (unfused reference path)."""

    def update(ctx: StepContext) -> StepContext:
        if ctx.grads is None:
            raise ValueError("apply_sgld_update needs a gradients() stage first")
        noise = ctx.noise if ctx.noise is not None else tree_zeros_like(ctx.params)
        return ctx._replace(params=sgld_apply(ctx.params, ctx.grads, ctx.gamma, noise))

    return stateless(update, "apply_sgld_update")


def fused_update(sigma: float) -> SamplerTransform:
    """Commit through the Pallas fused kernel: noise is generated *in VMEM*
    (counter-based threefry seeded from this step's noise key) and the
    update is one read of (x, g) + one write of x' — replacing the
    ``langevin_noise() + apply_sgld_update()`` pair in the hot path."""

    def update(ctx: StepContext) -> StepContext:
        if ctx.grads is None:
            raise ValueError("fused_update needs a gradients() stage first")
        scale = jnp.sqrt(2.0 * sigma * ctx.gamma)
        params = fused_langevin_update(ctx.params, ctx.grads,
                                       _key_bits(ctx.key_noise), ctx.gamma,
                                       scale)
        return ctx._replace(params=params)

    return stateless(update, "fused_update")


def _oracle_grads(grad_fn: GradFn, params: PyTree, batch: Any,
                  has_aux: bool):
    """Evaluate ``grad_fn`` at ``params`` under either batch contract:
    a plain batch calls the oracle once; a :class:`MaskedBatch` vmaps the
    *per-example* oracle over the padded bucket axis and masked-mean
    reduces, exactly as :func:`masked_gradients` does.  Returns
    ``(grads, aux)`` (aux ``None`` without ``has_aux``)."""
    if isinstance(batch, MaskedBatch):
        out = jax.vmap(lambda e: grad_fn(params, e))(batch.data)
        per_grads, per_aux = out if has_aux else (out, None)
        grads = masked_mean(per_grads, batch.size)
        aux = masked_mean(per_aux, batch.size) if has_aux else None
        return grads, aux
    out = grad_fn(params, batch)
    return out if has_aux else (out, None)


class SVRGState(NamedTuple):
    """Carry of :func:`svrg_gradients`: the control-variate anchor.

    ``anchor`` is the snapshot :math:`\\tilde X` the correction is centered
    on (same pytree structure as the params) and ``anchor_grad`` the full
    gradient :math:`\\mu = \\nabla U(\\tilde X)` evaluated at it.  Both live
    in the sampler's scanned carry, so an anchor refresh is a ``lax.cond``
    inside the jitted chunk — epochs never retrace.
    """

    anchor: PyTree       # pytree like params
    anchor_grad: PyTree  # pytree like params


def svrg_gradients(grad_fn: GradFn, full_grad_fn: Callable[[PyTree], PyTree],
                   *, anchor_every: int, has_aux: bool = False
                   ) -> SamplerTransform:
    """SVRG-Langevin gradient oracle: minibatch gradient with a
    control-variate correction against a periodically refreshed full-data
    anchor (Dubey et al.; stale-gradient variance analysis in Chen et al.).

    The committed gradient is

    ``g_k = grad_fn(x_hat_k, B_k) - grad_fn(anchor, B_k) + full_grad_fn(anchor)``

    — unbiased for the full gradient at the read point ``x_hat_k``, with the
    minibatch variance shrinking as the iterate approaches the anchor.  The
    anchor ``(params, full gradient)`` pair is transform state, i.e. part of
    the scanned carry: every ``anchor_every`` commits a ``lax.cond`` branch
    re-anchors at the *current* iterate and pays one full-gradient
    evaluation, so refreshes happen inside the jitted scan and never
    retrace, regardless of how the driver chunks the step loop.

    ``grad_fn`` follows the surrounding batch contract: called directly on a
    plain batch, vmapped per example and masked-mean reduced on a
    :class:`MaskedBatch` (the heterogeneous bucket-padded executor path).
    ``full_grad_fn(params)`` must close over the full dataset and return a
    gradient pytree.  ``aux`` (under ``has_aux``) comes from the read-point
    minibatch term only.
    """
    if anchor_every < 1:
        raise ValueError(f"anchor_every must be >= 1, got {anchor_every}")

    def init(params):
        # the zero anchor_grad is never read: step 0 satisfies
        # step % anchor_every == 0, so the first commit re-anchors first.
        # the anchor is a fresh copy — aliasing the live params buffer
        # would make the engines' donated carry donate it twice.
        return SVRGState(anchor=jax.tree_util.tree_map(jnp.array, params),
                         anchor_grad=tree_zeros_like(params))

    def update(ctx: StepContext, state: SVRGState):
        def refresh(_):
            return SVRGState(anchor=ctx.params,
                             anchor_grad=full_grad_fn(ctx.params))

        state = jax.lax.cond(ctx.step % anchor_every == 0, refresh,
                             lambda s: s, state)
        grads, aux = _oracle_grads(grad_fn, ctx.x_hat, ctx.batch, has_aux)
        anchor_grads, _ = _oracle_grads(grad_fn, state.anchor, ctx.batch,
                                        has_aux)
        corrected = jax.tree_util.tree_map(
            lambda g, ga, mu: g - ga + mu.astype(g.dtype),
            grads, anchor_grads, state.anchor_grad)
        return ctx._replace(grads=corrected, aux=aux), state

    return SamplerTransform(init, update, "svrg_gradients")


def stale_correction(strength: float = 1.0,
                     gamma_scale: float = 0.0) -> SamplerTransform:
    """Stale-gradient compensation for delayed reads (Chen et al.,
    *Stochastic Gradient MCMC with Stale Gradients*).

    Chen et al. show the bias and MSE of stale-gradient SG-MCMC grow with
    the staleness ``tau_k`` while the estimation variance does not, and that
    staleness-aware step-size selection recovers the fresh-gradient
    convergence rate.  This transform applies both halves, reading the
    *endogenous* staleness the executor derives from its
    :class:`~repro.cluster.schedule.WorkerSchedule`
    (``version - read_version``, surfaced as ``ctx.delay``):

    - **gradient term** — a first-order Taylor compensation of the stale
      gradient toward the fresh read point, with the Hessian approximated
      by the diagonal empirical Fisher (outer product of the gradient with
      itself): ``g <- g + strength * g * g * (X_k - X_hat_k)``;
    - **step-size term** — ``gamma <- gamma / (1 + gamma_scale * tau_k)``,
      the staleness-aware schedule shrink (``gamma_scale=0`` disables it).

    Both terms are selected per commit on ``tau_k > 0``, so a fresh read
    (``tau_k = 0``) commits **bitwise-identically** to the uncorrected
    chain (pinned in ``tests/test_zoo.py``).  Compose it directly after the
    gradient stage; it is contract-agnostic (plain or masked batches) since
    it only rewrites ``ctx.grads`` / ``ctx.gamma``.
    """

    def update(ctx: StepContext) -> StepContext:
        if ctx.grads is None:
            raise ValueError("stale_correction needs a gradients() stage "
                             "first")
        is_stale = ctx.delay > 0
        corrected = jax.tree_util.tree_map(
            lambda g, x, xh: jnp.where(
                is_stale,
                g + jnp.asarray(strength, g.dtype) * g * g
                * (x - xh).astype(g.dtype),
                g),
            ctx.grads, ctx.params, ctx.x_hat)
        gamma = ctx.gamma / (1.0 + jnp.asarray(gamma_scale, jnp.float32)
                             * jnp.where(is_stale,
                                         ctx.delay.astype(jnp.float32), 0.0))
        return ctx._replace(grads=corrected, gamma=gamma)

    return stateless(update, "stale_correction")


def sghmc_update(sigma: float, *, friction: float = 1.0,
                 precond: Any = None,
                 noise_dtype=jnp.float32) -> SamplerTransform:
    """Commit one SGHMC step: momentum buffer + friction + injected noise
    (the non-log-concave workhorse motivated by Zou, Xu & Gu's faster
    SGLD-family rates; momentum state rides the sampler carry and
    checkpoint-round-trips with it).

    The underdamped Langevin SDE ``dX = V dt``, ``dV = -grad U dt
    - a V dt + sqrt(2 a sigma) dW`` discretized Euler-style at step size
    ``gamma_k`` (Chen, Fox & Guestrin 2014):

    ``V_{k+1} = (1 - gamma_k a) V_k - gamma_k P grad + sqrt(2 a sigma
    gamma_k) sqrt(P) G_k``;  ``X_{k+1} = X_k + gamma_k V_{k+1}``

    where ``a = friction`` and ``P = precond`` is an optional diagonal
    (inverse-mass) preconditioner — a scalar or a pytree shaped like the
    params (the practical variant that drops the ``Gamma`` correction
    term).  Replaces the ``langevin_noise() + apply_sgld_update()`` pair;
    the gradient is whatever the upstream stages left in ``ctx.grads``, so
    it composes with :func:`delay_read`, :func:`svrg_gradients`, and
    :func:`stale_correction` unchanged.
    """
    if friction <= 0.0:
        raise ValueError(f"friction must be > 0, got {friction}")

    def init(params):
        return tree_zeros_like(params)  # momentum buffer V_0 = 0

    def precond_tree(params):
        """Normalize ``precond`` to one diagonal factor per leaf.  A None
        is the identity, a scalar broadcasts to every leaf, and a
        params-shaped pytree is taken leafwise (scalars are detected by
        value, not treedef — a bare float has the same single-leaf treedef
        as single-array params)."""
        if precond is None:
            return jax.tree_util.tree_map(
                lambda p: jnp.asarray(1.0, p.dtype), params)
        if (not isinstance(precond, (list, tuple, dict))
                and jnp.ndim(precond) == 0):
            return jax.tree_util.tree_map(
                lambda p: jnp.asarray(precond, p.dtype), params)
        return jax.tree_util.tree_map(
            lambda p, f: jnp.asarray(f, p.dtype), params, precond)

    def update(ctx: StepContext, momentum):
        if ctx.grads is None:
            raise ValueError("sghmc_update needs a gradients() stage first")
        scale = jnp.sqrt(2.0 * friction * sigma * ctx.gamma)
        noise = noise_like(ctx.key_noise, ctx.params, scale, noise_dtype)

        def step_v(v, g, n, p):
            decay = (1.0 - ctx.gamma * friction).astype(v.dtype)
            return (decay * v
                    - ctx.gamma.astype(v.dtype) * p.astype(v.dtype)
                    * g.astype(v.dtype)
                    + jnp.sqrt(p).astype(v.dtype) * n.astype(v.dtype))

        momentum = jax.tree_util.tree_map(step_v, momentum, ctx.grads,
                                          noise, precond_tree(ctx.params))
        params = jax.tree_util.tree_map(
            lambda x, v: (x + ctx.gamma.astype(x.dtype)
                          * v.astype(x.dtype)).astype(x.dtype),
            ctx.params, momentum)
        return ctx._replace(params=params, noise=noise), momentum

    return SamplerTransform(init, update, "sghmc_update")


def pipeline_overlap() -> SamplerTransform:
    """Swap this step's gradient for the previous one (tau=1 on the gradient
    sequence).  The fresh gradient's all-reduce has no consumer this step,
    so XLA overlaps it with the next step's compute."""

    def init(params):
        return tree_zeros_like(params)

    def update(ctx: StepContext, pending):
        if ctx.grads is None:
            raise ValueError("pipeline_overlap needs a gradients() stage first")
        return ctx._replace(grads=pending), ctx.grads

    return SamplerTransform(init, update, "pipeline_overlap")


def delay_read(policy: DelayPolicy) -> SamplerTransform:
    """Maintain the iterate ring buffer and set the stale read point.

    The last commit is pushed at the *start* of the step (value-identical to
    pushing at the end of the previous step, and it keeps the ring state
    local to this transform instead of special-cased in the driver state).
    """

    def init(params):
        return delay_lib.init_ring(params, policy.tau)

    def update(ctx: StepContext, ring):
        ring = delay_lib.push(ring, ctx.params)
        return ctx._replace(x_hat=policy.read(ctx, ring)), ring

    return SamplerTransform(init, update, "delay_read")
