"""Per-architecture smoke tests (deliverable f): REDUCED variant of each
assigned architecture runs one forward/train step and one decode step on CPU,
asserting output shapes and no NaNs."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import samplers
from repro.configs import ARCH_IDS, ShapeConfig, get_reduced
from repro.data import make_batch
from repro.models.transformer import Model, init_params, loss_fn
from repro.train.loop import make_grad_fn

TRAIN_SHAPE = ShapeConfig("smoke_train", seq_len=64, global_batch=2,
                          kind="train")
DEC_SHAPE = ShapeConfig("smoke_dec", seq_len=32, global_batch=2, kind="decode")


@pytest.fixture(scope="module", params=ARCH_IDS)
def setup(request):
    cfg = replace(get_reduced(request.param), dtype="float32")
    model = Model(cfg, mesh=None)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return request.param, cfg, model, params


def test_forward_shapes_and_finite(setup):
    aid, cfg, model, params = setup
    batch = make_batch(cfg, TRAIN_SHAPE, jax.random.PRNGKey(1), "train")
    loss, metrics = loss_fn(model, params, batch)
    assert np.isfinite(float(loss)), aid
    assert float(loss) > 0


def test_sgld_train_step_updates_params(setup):
    aid, cfg, model, params = setup
    sampler = samplers.sgld("sync", make_grad_fn(model), has_aux=True,
                            gamma=1e-3, sigma=1e-8)
    state = sampler.init(params, jax.random.PRNGKey(2))
    batch = make_batch(cfg, TRAIN_SHAPE, jax.random.PRNGKey(3), "train")
    new_state, metrics = jax.jit(sampler.step)(state, batch, 0)
    assert np.isfinite(float(metrics["loss"]))
    # params changed and stayed finite
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), state.params,
        new_state.params)
    assert max(jax.tree_util.tree_leaves(diffs)) > 0
    for leaf in jax.tree_util.tree_leaves(new_state.params):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32)))), aid


def skip_latent_serving(cfg, model):
    """MLA models refuse the decode paths (no latent paged cache)."""
    if cfg.kv_lora_rank:
        with pytest.raises(NotImplementedError, match="latent paged cache"):
            model.init_cache(2, 8)
        pytest.skip("serving latent attention (MLA) needs a latent paged "
                    "cache, which the model refuses to fake")


def test_serve_step_shapes(setup):
    aid, cfg, model, params = setup
    skip_latent_serving(cfg, model)
    cache = model.init_cache(2, DEC_SHAPE.seq_len,
                             prefill_len=DEC_SHAPE.seq_len - 1)
    batch = make_batch(cfg, DEC_SHAPE, jax.random.PRNGKey(4), "decode")
    logits, new_cache = jax.jit(model.serve_step)(
        params, cache, batch["tokens"], batch["cur_pos"])
    assert logits.shape == (2, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32)))), aid


def test_decode_consistent_with_forward(setup):
    """Greedy next-token from decode path == argmax of last-position logits
    from the parallel forward (attention-only archs, exact cache replay)."""
    aid, cfg, model, params = setup
    if cfg.block_pattern[0] not in ("attn_mlp", "attn_moe"):
        pytest.skip("recurrent archs covered by block tests")
    skip_latent_serving(cfg, model)
    if cfg.frontend:
        pytest.skip("frontend archs: positions differ between paths")
    S = 16
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, S), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    logits_full, _, _ = model.forward(params, {"tokens": tokens})
    cache = model.init_cache(2, S + 1)
    for t in range(S):
        logits_dec, cache = model.serve_step(params, cache, tokens[:, t:t + 1],
                                             jnp.int32(t))
    np.testing.assert_allclose(np.asarray(logits_full[:, -1]),
                               np.asarray(logits_dec[:, 0]),
                               atol=2e-3, rtol=1e-2)
