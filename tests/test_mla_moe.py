"""Latent attention and DeepSeek-V3 routing against a plain float32
reference (``mla_moe_reference.py``, written from the published modeling
code) on seeded random weights at a small size.

Tolerances: the program runs in float32 on the CPU, where XLA's float32
matmuls are exact float32 like the reference's ``highest``; what differs is
the order of the sums (flash attention's blocked online softmax, the
grouped matmul's tiles, the gated sum over a token's experts), which moves
a float32 result by a few ulps of its largest terms.  So values are held to
2e-5 relative to their scale, gradients to 1e-4 of the largest element
(a gradient sums many more such terms).
"""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mla_moe_reference as ref
from repro import samplers
from repro.cluster import ClusterEngine
from repro.configs import get_reduced
from repro.models import moe as moe_lib
from repro.models.mla import apply_mla, init_mla, rope_halves
from repro.models.transformer import Model, init_params, loss_fn
from repro.obs.metrics import registry
from repro.train.loop import make_grad_fn

RTOL = 2e-5
GTOL = 1e-4


def close(a, b, tol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= tol * scale, np.abs(a - b).max() / scale


def bias_profile(cfg, seed=0):
    """A fixed routing bias with one strongly favoured expert a layer."""
    rng = np.random.default_rng(seed)
    b = 0.05 * rng.standard_normal((cfg.num_moe_layers, cfg.num_experts))
    b[:, 1] += 0.3
    return tuple(tuple(float(v) for v in row) for row in b)


@pytest.fixture(scope="module")
def cfg():
    c = replace(get_reduced("moonlight-16b-a3b"), dtype="float32")
    return replace(c, score_correction_bias=bias_profile(c))


def test_rope_halves_permutes_pairs():
    x = jnp.arange(8.0)
    np.testing.assert_array_equal(rope_halves(x), [0, 2, 4, 6, 1, 3, 5, 7])


@pytest.mark.parametrize("seq", [16, 1024])
def test_mla_matches_reference(cfg, seq):
    """Naive attention at 16 tokens, the blocked flash path at 1024."""
    p = init_mla(jax.random.PRNGKey(0), cfg, jnp.float32)
    p["kv_norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                                 p["kv_norm"].shape)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, cfg.d_model))
    close(apply_mla(p, x, cfg, jnp.arange(seq)), ref.mla(p, x, cfg))


def test_mla_gradients_match_reference(cfg):
    p = init_mla(jax.random.PRNGKey(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 1024, cfg.d_model))

    def f(p, fn):
        return jnp.sum(jnp.sin(fn(p, x)))

    g = jax.grad(f)(p, lambda p, x: apply_mla(p, x, cfg, jnp.arange(1024)))
    gr = jax.grad(f)(p, lambda p, x: ref.mla(p, x, cfg))
    for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(gr)):
        close(a, b, GTOL)


def test_gating_matches_reference(cfg):
    """Sigmoid scores, the bias used for the choice only, gates normalised
    over the chosen k and scaled by 2.446."""
    p = moe_lib.init_moe(jax.random.PRNGKey(5), cfg, jnp.float32)
    bias = jnp.asarray(cfg.score_correction_bias[0])
    x = jax.random.normal(jax.random.PRNGKey(6), (64, cfg.d_model))
    idx, gates, aux = moe_lib.route(dict(p, **{moe_lib.BIAS: bias}), x, cfg)
    dense = np.zeros((64, cfg.num_experts))
    np.put_along_axis(dense, np.asarray(idx), np.asarray(gates), axis=1)
    want = np.asarray(ref.gate(x, p["router"], bias, cfg))
    close(dense, want)
    assert float(aux) == 0.0
    np.testing.assert_allclose(dense.sum(1), 2.446, rtol=1e-5)
    # the bias moved the choice: without it another set is chosen
    idx0, _, _ = moe_lib.route(p, x, cfg)
    assert (np.sort(np.asarray(idx0), 1) != np.sort(np.asarray(idx), 1)).any()
    # and the gates are the unbiased scores, renormalised
    scores = jax.nn.sigmoid(x @ p["router"])
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx), 1)
    close(gates, 2.446 * chosen / chosen.sum(1, keepdims=True))


def test_moe_layer_matches_reference(cfg):
    """The dropless layer over a share of 4 of 8 experts from expert 2."""
    c = replace(cfg, experts_held=4, expert_offset=2)
    p = moe_lib.init_moe(jax.random.PRNGKey(7), c, jnp.float32)
    bias = jnp.asarray(c.score_correction_bias[0])
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, c.d_model))
    pb = dict(p, **{moe_lib.BIAS: bias})
    y, _, load = moe_lib.apply_moe(pb, x, c)
    close(y, ref.moe(p, x, bias, c, offset=2))
    np.testing.assert_array_equal(load, ref.loads(p, x, bias, c, 4, 2))


def test_eight_shares_sum_to_the_uncut_layer(cfg):
    """Eight devices of two experts each: their partials, with the shared
    experts each computes counted once, add up to the uncut layer."""
    c = replace(cfg, num_experts=16, experts_per_token=4,
                score_correction_bias=())
    p = moe_lib.init_moe(jax.random.PRNGKey(9), c, jnp.float32)
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(10), (16,))
    p[moe_lib.BIAS] = bias
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 16, c.d_model))
    total, held = 0.0, 0
    for s in range(8):
        cs = replace(c, experts_held=2, expert_offset=2 * s)
        ps = dict(p, **{k: p[k][2 * s:2 * s + 2]
                        for k in ("w_gate", "w_up", "w_down")})
        y, _, load = moe_lib.apply_moe(ps, x, cs)
        total = total + y
        held += int(load.sum())
    shared = ref.swiglu(x, p["shared_w_gate"], p["shared_w_up"],
                        p["shared_w_down"])
    close(total - 7 * shared, ref.moe(p, x, bias, c))
    assert held == 2 * 16 * 4  # every (token, expert) pair, none dropped


@pytest.fixture(scope="module")
def model_setup(cfg):
    c = replace(cfg, experts_held=4, expert_offset=2)
    params = init_params(jax.random.PRNGKey(12), c)
    # norm scales off 1, so that a norm the program skipped would show
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(13), a.shape)
        if "norm" in str(path[-1]) else a, params)
    tokens = jax.random.randint(jax.random.PRNGKey(14), (2, 33), 0,
                                c.vocab_size)
    return c, params, tokens


def test_full_model_loss_and_gradients_match_reference(model_setup):
    c, params, tokens = model_setup
    biases = jnp.asarray(c.score_correction_bias)
    model = Model(c)
    (val, met), g = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(model, p, {"tokens": tokens}), has_aux=True))(params)
    rval, rg = jax.jit(jax.value_and_grad(ref.loss), static_argnums=(2,))(
        params, tokens, c, biases)
    assert abs(float(val) - float(rval)) < RTOL * abs(float(rval))
    assert float(val) == float(met["ce"])  # noaux_tc: cross-entropy alone
    for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(rg)):
        close(a, b, GTOL)
    assert met["expert_tokens"].shape == (c.num_moe_layers, 4)


def test_wcon_commits_through_cluster_engine(model_setup):
    """Four W-Con commits at tau 2 through ClusterEngine (no noise) follow
    the reference's gradient steps at the stale iterates the schedule
    names; the held assignments reach the executor's counter."""
    c, params, tokens = model_setup
    biases = jnp.asarray(c.score_correction_bias)
    gamma, delays = 0.05, [0, 1, 2, 2]
    sampler = samplers.sgld("consistent", make_grad_fn(Model(c)),
                            has_aux=True, tau=2, gamma=gamma, sigma=0.0)
    engine = ClusterEngine(sampler, num_chains=1, chunk_size=4,
                           collect_aux=True)
    counter = registry().counter("moe.assignments_held")
    before = counter.value
    batches = {"tokens": jnp.stack([jnp.roll(tokens, k, axis=1)
                                    for k in range(4)])}
    state = engine.init(params, jax.random.PRNGKey(15))
    state, aux = engine.run(state, steps=4, schedule=np.asarray(delays),
                            batches=batches)
    ring, x = [params], params
    grad = jax.jit(jax.value_and_grad(ref.loss), static_argnums=(2,))
    for k, tau in enumerate(delays):
        val, g = grad(ring[k - tau], batches["tokens"][k], c, biases)
        assert abs(float(aux["loss"][k, 0]) - float(val)) < 1e-4 * float(val)
        x = jax.tree_util.tree_map(lambda a, b: a - gamma * b, x, g)
        ring.append(x)
    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(x)):
        close(np.asarray(a)[0], b, GTOL)
    held = int(np.asarray(aux["expert_tokens"]).sum())
    assert counter.value - before == held
    assert held > 0
