"""End-to-end behaviour: the paper's experiments at test scale.

These are the system-level assertions behind EXPERIMENTS.md §Repro-*:
(1) SGLD (all read models) samples the correct regression posterior,
(2) async modes tolerate realistic simulated delays,
(3) RICA objective decreases under SGLD,
(4) the theory-prescribed (gamma_eps, n_eps) reaches the epsilon ball.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import samplers
from repro.core import (
    PolyRegression,
    ProblemConstants,
    Quadratic,
    RICA,
    WorkerModel,
    gamma_eps_w2,
    simulate_async,
)
from repro.metrics import w2_to_gaussian


@pytest.fixture(scope="module")
def reg():
    return PolyRegression.make(jax.random.PRNGKey(0), nu_std=0.1)


def _run_regression(reg, mode, tau, steps=8000, sigma=1e-3, batch=256,
                    seed=0):
    gamma = 2e-4

    def grad(p, key):
        batch_data = reg.sample_batch(key, batch)
        return jax.grad(reg.value)(p, batch_data)

    sampler = samplers.sgld(
        mode, grad, gamma=gamma, sigma=sigma,
        tau=tau if mode in ("consistent", "inconsistent") else 0)
    mu, cov, _ = reg.posterior_moments(sigma=sigma)
    state = sampler.init(mu + 0.5, jax.random.PRNGKey(seed))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), steps)
    if mode in ("consistent", "inconsistent"):
        trace = simulate_async(WorkerModel(num_workers=8, seed=seed), steps,
                               seed=seed)
        delays = jnp.asarray(np.minimum(trace.delays, tau))
    else:
        delays = jnp.zeros((steps,), jnp.int32)
    state, traj = jax.jit(lambda s: sampler.run(s, keys, delays))(state)
    return np.asarray(traj), mu, cov


@pytest.mark.slow
@pytest.mark.parametrize("mode,tau", [("sync", 0), ("consistent", 8),
                                      ("inconsistent", 8), ("pipeline", 0)])
def test_regression_posterior_all_modes(reg, mode, tau):
    """Paper §3.2: every read model reaches a small W2 to the posterior."""
    traj, mu, cov = _run_regression(reg, mode, tau)
    w2 = float(w2_to_gaussian(jnp.asarray(traj[3000:]), mu, cov))
    w2_start = float(np.linalg.norm(traj[0] - np.asarray(mu)))
    assert w2 < 0.25 * w2_start, (mode, w2, w2_start)


@pytest.mark.slow
def test_async_matches_sync_convergence(reg):
    """Paper's headline: async convergence-per-iteration ~ sync."""
    t_sync, mu, cov = _run_regression(reg, "sync", 0)
    t_async, _, _ = _run_regression(reg, "consistent", 8)
    w_sync = float(w2_to_gaussian(jnp.asarray(t_sync[4000:]), mu, cov))
    w_async = float(w2_to_gaussian(jnp.asarray(t_async[4000:]), mu, cov))
    assert w_async < 3.0 * w_sync + 0.05, (w_sync, w_async)


@pytest.mark.slow
def test_rica_objective_decreases():
    """Paper §3.3: SGLD on RICA drives the (non-convex) objective down."""
    rica = RICA(patch_dim=64, num_features=32)
    w0 = rica.init_params(jax.random.PRNGKey(0))

    def grad(p, key):
        return rica.grad(p, rica.sample_batch(key, 256))

    sampler = samplers.sgld("consistent", grad, gamma=2e-3, sigma=1e-6, tau=4)
    state = sampler.init(w0, jax.random.PRNGKey(1))
    keys = jax.random.split(jax.random.PRNGKey(2), 400)
    from repro.core import constant_delays
    delays = jnp.asarray(constant_delays(4, 400).delays)
    state, _ = jax.jit(lambda s: sampler.run(s, keys, delays,
                                             collect=False))(state)
    key_eval = jax.random.PRNGKey(3)
    before = float(rica.value(w0, rica.sample_batch(key_eval, 512)))
    after = float(rica.value(state.params, rica.sample_batch(key_eval, 512)))
    assert after < 0.8 * before, (before, after)


@pytest.mark.slow
def test_theory_prescription_reaches_epsilon():
    """Corollary 2.1 W2 variant at small scale: running at (gamma_eps, n_eps)
    lands inside the epsilon ball (constants are conservative)."""
    quad = Quadratic.make(jax.random.PRNGKey(1), d=2, m=1.0, L=2.0)
    eps = 0.25
    sigma = 0.1
    tau = 3
    c = ProblemConstants(m=quad.m, L=quad.L, d=2, G=4.0, sigma=sigma, tau=tau,
                         w2sq_0=float(jnp.sum(quad.x_star**2)))
    gamma = gamma_eps_w2(c, eps)
    n = min(60_000, 2 * int(np.ceil(np.log(4 * c.w2sq_0 / eps) / (gamma * c.m))))
    sampler = samplers.sgld("consistent", lambda p, b: quad.grad(p, b),
                            gamma=float(gamma), sigma=sigma, tau=tau)
    from repro.core import constant_delays
    delays = jnp.asarray(constant_delays(tau, n).delays)
    batches = jnp.zeros((n, 1))

    # the W2 bound is on the LAW of X_n: estimate from independent chains
    def chain(key):
        st = sampler.init(jnp.zeros(2), key)
        st, _ = sampler.run(st, batches, delays, collect=False)
        return st.params

    finals = jax.jit(jax.vmap(chain))(
        jax.random.split(jax.random.PRNGKey(2), 128))
    w2 = float(w2_to_gaussian(finals, quad.x_star,
                              jnp.diag(quad.stationary_cov(sigma))))
    assert w2**2 < eps, (w2**2, eps, gamma, n)
