"""Unified Engine: no per-delay retraces, scan-chunking speedup over the
per-step host loop, hooks."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import samplers
from repro.core import Quadratic
from repro.train.engine import Engine, checkpoint_hook, log_hook

STEPS = 40


@pytest.fixture(scope="module")
def quad_sampler():
    quad = Quadratic.make(jax.random.PRNGKey(0), d=4, m=1.0, L=3.0)
    return samplers.sgld("consistent", lambda p, b: quad.grad(p, b),
                         gamma=0.01, sigma=0.5, tau=4)


def test_no_retrace_across_delay_values(quad_sampler):
    """Distinct realized delays must NOT retrigger compilation: the old
    loops passed python ints (one XLA program per delay value), the Engine
    feeds delays as traced int32 arrays."""
    engine = Engine(quad_sampler, chunk_size=10)
    delays = np.asarray([0, 1, 2, 3, 4] * 8)  # 5 distinct values, 4 chunks
    state = quad_sampler.init(jnp.zeros(4), jax.random.PRNGKey(1))
    state, _ = engine.run(state, steps=STEPS, delays=delays)
    assert engine.num_traces == 1, engine.num_traces
    # a remainder chunk is the only legitimate second trace
    state, _ = engine.run(state, steps=15, delays=delays)
    assert engine.num_traces == 2, engine.num_traces


def test_engine_faster_than_per_step_loop(quad_sampler):
    """Scan-chunking amortizes dispatch: one jit call per chunk instead of
    one per step must win wall-clock on a dispatch-bound problem."""
    steps = 600
    delays = jnp.asarray(np.random.default_rng(0).integers(0, 5, steps),
                         jnp.int32)
    batches = jnp.zeros((steps, 1))

    jstep = jax.jit(quad_sampler.step)
    state = quad_sampler.init(jnp.zeros(4), jax.random.PRNGKey(2))
    state, _ = jstep(state, batches[0], delays[0])  # compile
    jax.block_until_ready(state.params)
    t0 = time.time()
    for k in range(steps):
        state, _ = jstep(state, batches[k], delays[k])
    jax.block_until_ready(state.params)
    t_loop = time.time() - t0

    engine = Engine(quad_sampler, chunk_size=100, collect_aux=False)
    state = quad_sampler.init(jnp.zeros(4), jax.random.PRNGKey(2))
    state, _ = engine.run(state, steps=steps, batches=batches, delays=delays)
    jax.block_until_ready(state.params)  # warm (compiles the chunk)
    state = quad_sampler.init(jnp.zeros(4), jax.random.PRNGKey(2))
    t0 = time.time()
    state, _ = engine.run(state, steps=steps, batches=batches, delays=delays)
    jax.block_until_ready(state.params)
    t_engine = time.time() - t0

    assert t_engine < t_loop, (t_engine, t_loop)


def test_engine_matches_per_step_stepping(quad_sampler):
    delays = np.asarray([0, 2, 4, 1] * 10)
    batches = jnp.zeros((STEPS, 1))
    state = quad_sampler.init(jnp.zeros(4), jax.random.PRNGKey(3))
    jstep = jax.jit(quad_sampler.step)
    for k in range(STEPS):
        state, _ = jstep(state, batches[k], jnp.int32(delays[k]))
    engine = Engine(quad_sampler, chunk_size=7)  # remainder chunk included
    e_state = quad_sampler.init(jnp.zeros(4), jax.random.PRNGKey(3))
    e_state, _ = engine.run(e_state, steps=STEPS, batches=batches,
                            delays=delays)
    np.testing.assert_allclose(np.asarray(e_state.params),
                               np.asarray(state.params), rtol=1e-6, atol=1e-7)


def test_hooks_and_aux_collection(tmp_path, quad_sampler):
    quad = Quadratic.make(jax.random.PRNGKey(0), d=4, m=1.0, L=3.0)
    sampler = samplers.sgld(
        "sync", lambda p, b: (quad.grad(p, b), {"loss": quad.value(p, b)}),
        gamma=0.01, sigma=0.5, has_aux=True)
    seen = []
    lines = []
    ckpt = os.path.join(tmp_path, "engine_ckpt.npz")
    engine = Engine(
        sampler, chunk_size=10,
        hooks=[lambda step_end, state, aux: seen.append(step_end),
               log_hook(every=10, log_fn=lines.append),
               checkpoint_hook(ckpt, every=20)])
    state = sampler.init(jnp.zeros(4), jax.random.PRNGKey(4))
    state, aux = engine.run(state, steps=STEPS)
    assert seen == [10, 20, 30, 40]
    assert len(lines) == 4 and "loss" in lines[0]
    assert os.path.exists(ckpt)
    assert aux["loss"].shape == (STEPS,)
    assert np.all(np.isfinite(aux["loss"]))


def test_engine_generates_batches_on_device(quad_sampler):
    """batch_fn(key) is vmapped over a chunk of keys; trajectories match
    pre-stacked batches bit-for-bit."""
    quad = Quadratic.make(jax.random.PRNGKey(0), d=4, m=1.0, L=3.0,
                          grad_noise=0.5)

    def grad(p, batch):
        return quad.grad(p, None, key=batch["key"])

    sampler = samplers.sgld("sync", grad, gamma=0.01, sigma=0.5)

    def batch_fn(key):
        return {"key": jax.random.fold_in(key, 0)}

    engine = Engine(sampler, batch_fn=batch_fn, chunk_size=8)
    state = sampler.init(jnp.zeros(4), jax.random.PRNGKey(5))
    state, _ = engine.run(state, steps=24, key=jax.random.PRNGKey(6))
    assert np.all(np.isfinite(np.asarray(state.params)))


def test_checkpoint_hook_flushes_final_state(tmp_path, quad_sampler):
    """steps not a multiple of `every` must still save the final state."""
    ckpt = os.path.join(tmp_path, "flush_ckpt.npz")
    engine = Engine(quad_sampler, chunk_size=10,
                    hooks=[checkpoint_hook(ckpt, every=10)])
    state = quad_sampler.init(jnp.zeros(4), jax.random.PRNGKey(7))
    state, _ = engine.run(state, steps=25)
    with np.load(ckpt) as data:
        assert int(data["__step__"]) == 25


def test_engine_accepts_delay_trace_and_threads_commit_times(quad_sampler):
    from repro.core import constant_delays

    trace = constant_delays(3, STEPS)
    engine = Engine(quad_sampler, chunk_size=10)
    state = quad_sampler.init(jnp.zeros(4), jax.random.PRNGKey(8))
    state, aux = engine.run(state, steps=STEPS, delays=trace)
    np.testing.assert_array_equal(aux["commit_time"], trace.commit_times)

    # identical trajectory to passing the raw delays ndarray
    state2 = quad_sampler.init(jnp.zeros(4), jax.random.PRNGKey(8))
    state2, _ = engine.run(state2, steps=STEPS, delays=trace.delays)
    np.testing.assert_array_equal(np.asarray(state.params),
                                  np.asarray(state2.params))


def test_engine_rejects_delays_deeper_than_ring(quad_sampler):
    """tau=4 ring (depth 5) cannot serve staleness 5+: raise, don't clamp."""
    engine = Engine(quad_sampler, chunk_size=10)
    state = quad_sampler.init(jnp.zeros(4), jax.random.PRNGKey(9))
    delays = np.asarray([0, 1, 5, 2] * 10)
    with pytest.raises(ValueError, match="does not fit the iterate ring"):
        engine.run(state, steps=STEPS, delays=delays)
