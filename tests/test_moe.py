"""MoE routing / dispatch correctness (local path; sharded path covered by
test_sharding subprocess tests; DeepSeek-V3 gating and the expert share
against a reference in test_mla_moe)."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models.moe import _layer_local, apply_moe, init_moe, route
from repro.models.common import activation


@pytest.fixture(scope="module")
def cfg():
    return replace(get_reduced("phi3.5-moe-42b-a6.6b"), dtype="float32")


def test_top1_routing_selects_expert(cfg):
    """With a hand-built router, tokens go to the intended expert."""
    cfg1 = replace(cfg, experts_per_token=1, num_experts=4)
    p = init_moe(jax.random.PRNGKey(0), cfg1, jnp.float32)
    d = cfg1.d_model
    # router that routes by sign pattern of first feature
    router = jnp.zeros((d, 4)).at[0, 0].set(10.0).at[0, 1].set(-10.0)
    p = dict(p, router=router)
    xt = jnp.zeros((8, d)).at[:4, 0].set(1.0).at[4:, 0].set(-1.0)
    out, aux, _ = _layer_local(p, xt, cfg1, 4, 0, activation(cfg1.act))
    # expert 0 processes tokens 0..3, expert 1 tokens 4..7: outputs within
    # each group identical, across groups different
    o = np.asarray(out)
    np.testing.assert_allclose(o[0], o[1], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(o[4], o[5], rtol=1e-5, atol=1e-6)
    assert np.abs(o[0] - o[4]).max() > 1e-4


@pytest.mark.parametrize("held,offset", [(4, 0), (2, 0), (2, 2)])
def test_dropless_when_every_token_picks_one_expert(cfg, held, offset):
    """Every token routed to expert 0, far past any capacity a buffer of
    the mean load would give: all are processed where expert 0 is held, and
    none is where it is held elsewhere."""
    cfg1 = replace(cfg, experts_per_token=1, num_experts=4,
                   num_shared_experts=0)
    p = init_moe(jax.random.PRNGKey(1), replace(cfg1, experts_held=4),
                 jnp.float32)
    d = cfg1.d_model
    router = jnp.zeros((d, 4)).at[0, 0].set(10.0)  # everything -> expert 0
    p = dict(p, router=router, **{k: p[k][offset:offset + held]
                                  for k in ("w_gate", "w_up", "w_down")})
    xt = jax.random.normal(jax.random.PRNGKey(2), (32, d)).at[:, 0].set(1.0)
    out, _, load = _layer_local(p, xt, cfg1, held, offset,
                                activation(cfg1.act))
    o = np.asarray(out)
    if offset == 0:
        np.testing.assert_array_equal(load, [32] + [0] * (held - 1))
        h = jax.nn.silu(xt @ p["w_gate"][0]) * (xt @ p["w_up"][0])
        np.testing.assert_allclose(o, h @ p["w_down"][0], rtol=1e-5,
                                   atol=1e-5)
    else:
        np.testing.assert_array_equal(load, [0] * held)
        assert not o.any()


def test_aux_loss_uniform_router_is_one(cfg):
    """Switch aux loss == 1 for a perfectly uniform router."""
    cfg1 = replace(cfg, num_experts=4, experts_per_token=1)
    p = init_moe(jax.random.PRNGKey(2), cfg1, jnp.float32)
    p = dict(p, router=jnp.zeros((cfg1.d_model, 4)))
    # logits all equal -> probs uniform; top-1 ties broken by index (all to
    # expert 0) -> aux = E * (1 * 1/E) = 1 for probs, frac_tokens=e0=1:
    # aux = E * sum(frac_tokens * frac_probs) = 4 * (1*0.25) = 1
    xt = jax.random.normal(jax.random.PRNGKey(3), (64, cfg1.d_model)) * 0.0
    _, _, aux = route(p, xt, cfg1)
    assert float(aux) == pytest.approx(1.0, rel=1e-3)


def test_moe_apply_differentiable(cfg):
    cfg1 = replace(cfg, num_experts=4, experts_per_token=2)
    p = init_moe(jax.random.PRNGKey(4), cfg1, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, cfg1.d_model))

    def loss(p):
        y, aux, _ = apply_moe(p, x, cfg1, mesh=None)
        return jnp.sum(y**2) + aux

    g = jax.grad(loss)(p)
    gn = sum(float(jnp.sum(v**2)) for v in jax.tree_util.tree_leaves(g))
    assert np.isfinite(gn) and gn > 0
    # router must receive gradient (through combine weights)
    assert float(jnp.sum(g["router"]**2)) > 0


def test_shared_expert_contributes(cfg):
    cfg1 = replace(cfg, num_experts=4, experts_per_token=2,
                   num_shared_experts=1)
    p = init_moe(jax.random.PRNGKey(6), cfg1, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 4, cfg1.d_model))
    y1, _, _ = apply_moe(p, x, cfg1, mesh=None)
    p2 = dict(p, shared_w_down=jnp.zeros_like(p["shared_w_down"]))
    y2, _, _ = apply_moe(p2, x, cfg1, mesh=None)
    assert float(jnp.abs(y1 - y2).max()) > 1e-5
