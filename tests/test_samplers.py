"""Composable sampler API: preset parity with an in-file oracle of the
paper's update (all four modes), mode/tau validation, chain composability,
delay policies, fused-vs-unfused commit, ring wraparound."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import samplers
from repro.core import Quadratic, constant_delays
from repro.core import delay as delay_lib
from repro.samplers.policies import ConstantDelay, PerCoordinateDelay
from repro.samplers.transforms import noise_like, sgld_apply
from repro.utils import tree_zeros_like

GAMMA = 0.01
SIGMA = 0.5
STEPS = 60


@pytest.fixture(scope="module")
def quad():
    return Quadratic.make(jax.random.PRNGKey(0), d=4, m=1.0, L=3.0)


def legacy_reference_run(mode, grad, x0, key, gamma, sigma, tau, delays, steps):
    """Verbatim pre-redesign ``SGLDSampler.step`` math (the parity oracle)."""
    ring = (delay_lib.init_ring(x0, tau)
            if mode in ("consistent", "inconsistent") else None)
    pending = tree_zeros_like(x0) if mode == "pipeline" else None
    params = x0
    traj = []
    for k in range(steps):
        key, k_noise, k_delay = jax.random.split(key, 3)
        g_step = jnp.asarray(gamma, jnp.float32)
        scale = jnp.sqrt(2.0 * sigma * g_step)
        noise = noise_like(k_noise, params, scale, jnp.float32)
        d = jnp.asarray(delays[k], jnp.int32)
        if mode == "sync":
            params = sgld_apply(params, grad(params, None), g_step, noise)
        elif mode == "pipeline":
            new_grad = grad(params, None)
            params = sgld_apply(params, pending, g_step, noise)
            pending = new_grad
        else:
            if mode == "consistent":
                x_hat = delay_lib.read_consistent(ring, d)
            else:
                cds = delay_lib.sample_coordinate_delays(k_delay, ring, d)
                x_hat = delay_lib.read_inconsistent(ring, cds)
            params = sgld_apply(params, grad(x_hat, None), g_step, noise)
            ring = delay_lib.push(ring, params)
        traj.append(params)
    return jnp.stack(traj)


def _delays_for(tau, steps):
    if tau:
        return jnp.asarray(constant_delays(tau, steps).delays)
    return jnp.zeros((steps,), jnp.int32)


@pytest.mark.parametrize("mode,tau", [("sync", 0), ("pipeline", 0),
                                      ("consistent", 4), ("inconsistent", 4)])
def test_preset_matches_legacy_sampler(quad, mode, tau):
    """samplers.sgld(mode=...) reproduces the string-dispatched sampler's
    trajectory under a fixed PRNG key (fp32 allclose; the residual is
    jit-vs-eager fusion, not algorithm)."""
    grad = lambda p, b: quad.grad(p, b)  # noqa: E731
    delays = _delays_for(tau, STEPS)
    want = legacy_reference_run(mode, grad, jnp.zeros(4), jax.random.PRNGKey(1),
                                GAMMA, SIGMA, tau, delays, STEPS)
    sampler = samplers.sgld(mode, grad, gamma=GAMMA, sigma=SIGMA, tau=tau)
    state = sampler.init(jnp.zeros(4), jax.random.PRNGKey(1))
    _, got = jax.jit(lambda s: sampler.run(s, jnp.zeros((STEPS, 1)), delays))(state)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("mode,tau", [("lockstep", 0), ("consistent", 0),
                                      ("inconsistent", 0)])
def test_presets_refuse_unknown_mode_and_stale_read_without_tau(quad, mode,
                                                                tau):
    """Mode and tau are validated once, where every preset builds its read
    stage: an unknown mode, and a stale read with no ring (tau 0), raise
    before any state exists."""
    grad = lambda p, b: quad.grad(p, b)  # noqa: E731
    for preset in (samplers.sgld, samplers.sghmc):
        with pytest.raises(ValueError, match=repr(mode)):
            preset(mode, grad, gamma=GAMMA, sigma=SIGMA, tau=tau)


def test_chain_composes_to_gradient_descent(quad):
    """With the noise stage omitted, the chain is plain delayed GD."""
    grad = lambda p, b: quad.grad(p, b)  # noqa: E731
    sampler = samplers.Sampler(
        samplers.chain(samplers.gradients(grad), samplers.apply_sgld_update()),
        gamma=0.05)
    state = sampler.init(jnp.ones(4) * 3.0, jax.random.PRNGKey(3))
    x = jnp.ones(4) * 3.0
    for _ in range(20):
        state, _ = sampler.step(state, None)
        x = x - 0.05 * grad(x, None)
    np.testing.assert_allclose(np.asarray(state.params), np.asarray(x),
                               rtol=1e-6)


def test_constant_delay_policy_equals_warmup_trace(quad):
    """ConstantDelay(tau) == TraceDelay fed the constant_delays warm-up
    trace (staleness can't exceed the commit count)."""
    grad = lambda p, b: quad.grad(p, b)  # noqa: E731
    tau, steps = 3, 25
    by_policy = samplers.sgld("consistent", grad, gamma=GAMMA, sigma=SIGMA,
                              tau=tau, delay_policy=ConstantDelay(tau))
    by_trace = samplers.sgld("consistent", grad, gamma=GAMMA, sigma=SIGMA,
                             tau=tau)
    delays = jnp.asarray(constant_delays(tau, steps).delays)
    s1 = by_policy.init(jnp.zeros(4), jax.random.PRNGKey(4))
    s2 = by_trace.init(jnp.zeros(4), jax.random.PRNGKey(4))
    _, t1 = jax.jit(lambda s: by_policy.run(s, jnp.zeros((steps, 1))))(s1)
    _, t2 = jax.jit(lambda s: by_trace.run(s, jnp.zeros((steps, 1)), delays))(s2)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))


def test_per_coordinate_policy_fused_gather_matches_reference(quad):
    grad = lambda p, b: quad.grad(p, b)  # noqa: E731
    delays = _delays_for(4, 20)
    ref = samplers.sgld("inconsistent", grad, gamma=GAMMA, sigma=SIGMA, tau=4)
    fused = samplers.sgld("inconsistent", grad, gamma=GAMMA, sigma=SIGMA,
                          tau=4,
                          delay_policy=PerCoordinateDelay(4, fused=True))
    s1 = ref.init(jnp.zeros(4), jax.random.PRNGKey(5))
    s2 = fused.init(jnp.zeros(4), jax.random.PRNGKey(5))
    _, t1 = jax.jit(lambda s: ref.run(s, jnp.zeros((20, 1)), delays))(s1)
    _, t2 = jax.jit(lambda s: fused.run(s, jnp.zeros((20, 1)), delays))(s2)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))


# ---------------------------------------------------------------------------
# fused Pallas commit vs unfused reference
# ---------------------------------------------------------------------------
def test_fused_update_equals_apply_update_at_zero_temperature(quad):
    """With sigma=0 both commit paths are x - gamma*g exactly."""
    grad = lambda p, b: quad.grad(p, b)  # noqa: E731
    ref = samplers.sgld("sync", grad, gamma=0.05, sigma=0.0)
    fus = samplers.sgld("sync", grad, gamma=0.05, sigma=0.0, fused=True)
    s1 = ref.init(jnp.ones(4), jax.random.PRNGKey(6))
    s2 = fus.init(jnp.ones(4), jax.random.PRNGKey(6))
    _, t1 = jax.jit(lambda s: ref.run(s, jnp.zeros((10, 1))))(s1)
    _, t2 = jax.jit(lambda s: fus.run(s, jnp.zeros((10, 1))))(s2)
    np.testing.assert_allclose(np.asarray(t1), np.asarray(t2),
                               rtol=1e-6, atol=1e-7)


def test_fused_update_noise_statistics():
    """At scale=1 the fused kernel's VMEM-generated noise is standard normal."""
    params = {"w": jnp.zeros((40_000,)), "b": jnp.zeros((300,))}
    grad = lambda p, b: tree_zeros_like(p)  # noqa: E731
    # sqrt(2 * sigma * gamma) = 1
    sampler = samplers.sgld("sync", grad, gamma=1.0, sigma=0.5, fused=True)
    state = sampler.init(params, jax.random.PRNGKey(7))
    state, _ = jax.jit(sampler.step)(state, None, 0)
    z = np.concatenate([np.asarray(x).ravel()
                        for x in jax.tree_util.tree_leaves(state.params)])
    assert abs(z.mean()) < 0.02
    assert abs(z.std() - 1.0) < 0.02


# ---------------------------------------------------------------------------
# ring buffer wraparound (satellite)
# ---------------------------------------------------------------------------
def test_ring_wraparound_after_more_than_depth_pushes():
    """After depth+k pushes the ring holds exactly the last ``depth``
    snapshots, reads walk them newest-to-oldest, and older snapshots are
    gone (overwritten in place)."""
    params = {"w": jnp.zeros((2,))}
    tau = 2  # depth = 3
    ring = delay_lib.init_ring(params, tau=tau)
    n_push = 2 * ring.depth + 1  # 7: wraps the ring twice
    for k in range(1, n_push + 1):
        ring = delay_lib.push(ring, {"w": jnp.full((2,), float(k))})
    for d in range(ring.depth):
        got = float(delay_lib.read_consistent(ring, d)["w"][0])
        assert got == float(n_push - d), (d, got)
    # beyond-depth delays clamp to the oldest retained snapshot
    assert float(delay_lib.read_consistent(ring, 99)["w"][0]) == float(
        n_push - tau)
    # every retained slot is one of the last `depth` pushes — nothing older
    vals = set(np.asarray(ring.history["w"])[:, 0].tolist())
    assert vals == {float(v) for v in range(n_push - tau, n_push + 1)}
    # head keeps cycling: another full wrap lands on the same slot index
    head_before = int(ring.head)
    for k in range(ring.depth):
        ring = delay_lib.push(ring, {"w": jnp.full((2,), 100.0 + k)})
    assert int(ring.head) == head_before
