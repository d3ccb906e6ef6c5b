"""Core sampler behaviour: all four modes, stationary statistics, paper
properties (tau robustness)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import samplers
from repro.core import Quadratic, constant_delays

SIGMA = 0.5
GAMMA = 0.01
N_STEPS = 15_000
BURN = 5_000


@pytest.fixture(scope="module")
def quad():
    return Quadratic.make(jax.random.PRNGKey(0), d=4, m=1.0, L=3.0)


def _run(quad, mode, tau=0, delays=None, steps=N_STEPS, seed=1):
    sampler = samplers.sgld(mode, lambda p, b: quad.grad(p, b), gamma=GAMMA,
                            sigma=SIGMA, tau=tau)
    state = sampler.init(jnp.zeros(4), jax.random.PRNGKey(seed))
    batches = jnp.zeros((steps, 1))
    if delays is None:
        delays = jnp.zeros((steps,), jnp.int32)
    state, traj = jax.jit(lambda s: sampler.run(s, batches, delays))(state)
    return np.asarray(traj)


@pytest.mark.parametrize("mode,tau", [("sync", 0), ("pipeline", 0),
                                      ("consistent", 4), ("inconsistent", 4)])
def test_stationary_distribution(quad, mode, tau):
    """For quadratic U, Langevin targets N(x*, sigma * A^-1): every read
    model must land near the closed-form moments (paper's core claim —
    delays do not destroy convergence in measure)."""
    delays = jnp.asarray(constant_delays(tau, N_STEPS).delays) if tau else None
    traj = _run(quad, mode, tau=tau, delays=delays)
    samp = traj[BURN:]
    target_var = np.asarray(quad.stationary_cov(SIGMA))
    assert np.allclose(samp.mean(0), np.asarray(quad.x_star), atol=0.15)
    assert np.allclose(samp.var(0), target_var, rtol=0.35)


def test_delay_increases_bias_not_order(quad):
    """Larger tau inflates the W2 error floor polynomially but must not
    diverge (Cor 2.1: same order, worse constants)."""
    errs = []
    for tau in (1, 4, 8):
        delays = jnp.asarray(constant_delays(tau, N_STEPS).delays)
        traj = _run(quad, "consistent", tau=tau, delays=delays)
        m = traj[BURN:].mean(0)
        errs.append(float(np.linalg.norm(m - np.asarray(quad.x_star))))
    assert max(errs) < 0.5  # no divergence even at tau=8
    assert all(np.isfinite(errs))


def test_decreasing_gamma_schedule_converges(quad):
    from repro.core.schedules import poly_decay

    # low temperature: this test checks the schedule mechanics (drift to
    # x*), not the stationary spread — keep estimator noise small
    sampler = samplers.sgld("sync", lambda p, b: quad.grad(p, b),
                            gamma=poly_decay(0.1, alpha=0.4, t0=10.0),
                            sigma=0.02)
    state = sampler.init(jnp.zeros(4) + 5.0, jax.random.PRNGKey(2))
    batches = jnp.zeros((N_STEPS, 1))
    delays = jnp.zeros((N_STEPS,), jnp.int32)
    _, traj = jax.jit(lambda s: sampler.run(s, batches, delays))(state)
    start_err = float(np.linalg.norm(5.0 - np.asarray(quad.x_star)))
    late_err = float(np.linalg.norm(np.asarray(traj[-2000:]).mean(0)
                                    - np.asarray(quad.x_star)))
    assert late_err < 0.4, late_err
    assert late_err < 0.1 * start_err


def test_pipeline_equals_one_step_stale_gradient(quad):
    """pipeline mode is exactly W-Con with tau=1 on the gradient sequence:
    with sigma=0 and constant gamma, params_{k+1} = params_k - g(params_{k-1})."""
    sampler = samplers.sgld("pipeline", lambda p, b: quad.grad(p, b),
                            gamma=0.1, sigma=0.0)
    state = sampler.init(jnp.ones(4), jax.random.PRNGKey(3))
    # manual reference
    x = jnp.ones(4)
    pending = jnp.zeros(4)
    for _ in range(5):
        state, _ = sampler.step(state, None, 0)
        g = quad.grad(x, None)
        x = x - 0.1 * pending
        pending = g
    np.testing.assert_allclose(np.asarray(state.params), np.asarray(x),
                               rtol=1e-5)


def test_aux_metrics_surface(quad):
    def grad_with_aux(p, b):
        return quad.grad(p, b), {"loss": quad.value(p, b)}

    sampler = samplers.sgld("sync", grad_with_aux, has_aux=True, gamma=GAMMA,
                            sigma=SIGMA)
    state = sampler.init(jnp.zeros(4), jax.random.PRNGKey(4))
    state, aux = sampler.step(state, None, 0)
    assert "loss" in aux and np.isfinite(float(aux["loss"]))


def test_sync_variance_reduction_vs_async_small_batch():
    """Paper §3: Sync effectively averages P gradients (larger batch);
    per-update gradient noise must be lower for sync."""
    quad = Quadratic.make(jax.random.PRNGKey(5), d=2, m=1.0, L=1.0,
                          grad_noise=1.0)
    key = jax.random.PRNGKey(6)

    def noisy_grad(p, key):
        return quad.grad(p, None, key=key)

    p0 = jnp.zeros(2)
    keys = jax.random.split(key, 256)
    singles = jnp.stack([noisy_grad(p0, k) for k in keys[:64]])
    summed = jnp.stack([
        jnp.mean(jnp.stack([noisy_grad(p0, k) for k in keys[i:i + 8]]), 0)
        for i in range(0, 256, 8)])
    assert float(summed.var(0).mean()) < float(singles.var(0).mean()) / 4
