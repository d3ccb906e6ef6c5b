"""cluster.serve: per-query predictive statistics bitwise-equal to the
single-device gather-then-reduce reference (sharded included), bucket
padding transparent to the statistics, one trace per shape bucket across a
mixed request stream, and checkpoint restore into the ensemble layout."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import samplers
from repro.checkpoint import restore_ensemble, save_checkpoint
from repro.cluster import (
    ClusterEngine,
    ServeEngine,
    bucket_size,
    ensemble_async,
    predictive_stats,
)
from repro.core import PolyRegression, WorkerModel
from repro.models import regression_predict

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
C = 8


@pytest.fixture(scope="module")
def reg():
    return PolyRegression.make(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def bank():
    return jax.random.normal(jax.random.PRNGKey(1), (C, 5))


@pytest.fixture(scope="module")
def reference(reg, bank):
    """The gather-then-reduce reference: the whole bank on one device, the
    unpadded query batch, the shared reduction — jitted like the engine."""
    predict = regression_predict(reg)
    qs = jnp.asarray((0.05, 0.5, 0.95), jnp.float32)

    @jax.jit
    def ref(params, queries):
        preds = jax.vmap(predict, in_axes=(0, None))(params, queries)
        return predictive_stats(preds, qs)

    return lambda queries: ref(bank, queries)


# ---------------------------------------------------------------------------
# bucket ladder
# ---------------------------------------------------------------------------
def test_bucket_size_defaults_to_powers_of_two():
    assert [bucket_size(n) for n in (1, 2, 3, 4, 5, 9, 33)] == \
        [1, 2, 4, 4, 8, 16, 64]
    with pytest.raises(ValueError):
        bucket_size(0)


def test_bucket_size_explicit_ladder_is_a_contract():
    assert bucket_size(5, buckets=(4, 16)) == 16
    with pytest.raises(ValueError, match="exceed the largest bucket"):
        bucket_size(17, buckets=(4, 16))


# ---------------------------------------------------------------------------
# statistics parity + padding transparency: the acceptance-criterion checks
# ---------------------------------------------------------------------------
def test_stats_bitwise_equal_gather_then_reduce(reg, bank, reference):
    engine = ServeEngine(predict_fn=regression_predict(reg), params=bank)
    z = jnp.linspace(-1.0, 1.0, 5)  # padded up to bucket 8
    res, ref = engine(z), reference(z)
    assert np.array_equal(np.asarray(res.mean), np.asarray(ref.mean))
    assert np.array_equal(np.asarray(res.var), np.asarray(ref.var))
    assert np.array_equal(np.asarray(res.quantiles), np.asarray(ref.quantiles))
    assert res.mean.shape == (5,) and res.quantiles.shape == (3, 5)


def test_bucket_padding_transparent_across_mixed_stream(reg, bank, reference):
    """Every request of a mixed stream must produce stats identical to its
    unpadded reference, while compiling at most one trace per bucket."""
    engine = ServeEngine(predict_fn=regression_predict(reg), params=bank)
    sizes = [3, 4, 2, 7, 8, 5, 1, 6, 4, 3]
    for i, n in enumerate(sizes):
        z = jax.random.uniform(jax.random.PRNGKey(i), (n,),
                               minval=-1.0, maxval=1.0)
        res, ref = engine(z), reference(z)
        for got, want in zip(res, ref):
            assert np.array_equal(np.asarray(got), np.asarray(want)), n
    assert engine.num_traces == len({bucket_size(n) for n in sizes})


def test_padding_never_consumes_the_callers_buffer(reg, bank):
    """donate_argnums applies to the engine's own padded buffer: a request
    exactly at a bucket boundary must leave the caller's array usable."""
    engine = ServeEngine(predict_fn=regression_predict(reg), params=bank)
    z = jnp.linspace(-1.0, 1.0, 4)  # exactly bucket 4, no padding needed
    engine(z)
    np.testing.assert_allclose(np.asarray(z)[-1], 1.0)  # not donated away


def test_host_padding_reuses_one_scratch_per_rung(reg, bank, reference):
    """Host-query padding must allocate one scratch buffer per (rung, leaf)
    and then rewrite it in place — zero allocations per request — without
    perturbing the statistics."""
    engine = ServeEngine(predict_fn=regression_predict(reg), params=bank)
    rng = np.random.default_rng(0)
    first = rng.uniform(-1.0, 1.0, 5).astype(np.float32)
    engine(first)
    assert engine.num_host_pad_allocs == 1  # rung 8 scratch created
    buf0 = engine._host_scratch.get(("pad", 0), (8,), np.float32)
    for i in range(6):  # same rung, distinct sizes: no new allocations
        z = rng.uniform(-1.0, 1.0, 5 + (i % 3)).astype(np.float32)
        res, ref = engine(z), reference(jnp.asarray(z))
        for got, want in zip(res, ref):
            assert np.array_equal(np.asarray(got), np.asarray(want)), i
    assert engine.num_host_pad_allocs == 1
    assert engine._host_scratch.get(("pad", 0), (8,), np.float32) is buf0
    engine(rng.uniform(-1.0, 1.0, 12).astype(np.float32))  # rung 16
    assert engine.num_host_pad_allocs == 2


def test_pytree_queries_pad_and_slice(reg, bank):
    """Dict-shaped query batches bucket on the shared leading axis."""

    def predict(w, batch):
        return reg.predict(w, reg.features(batch["z"])) + batch["offset"]

    engine = ServeEngine(predict_fn=predict, params=bank)
    batch = {"z": jnp.linspace(-1.0, 1.0, 3), "offset": jnp.zeros(3)}
    res = engine(batch)
    assert res.mean.shape == (3,)
    assert np.all(np.isfinite(np.asarray(res.mean)))


def test_quantile_order_matches_engine_quantiles(reg, bank):
    engine = ServeEngine(predict_fn=regression_predict(reg), params=bank,
                         quantiles=(0.1, 0.9))
    res = engine(jnp.linspace(-1.0, 1.0, 4))
    assert res.quantiles.shape == (2, 4)
    assert np.all(np.asarray(res.quantiles[0]) <= np.asarray(res.quantiles[1]))
    assert np.all(np.asarray(res.var) >= 0.0)
    assert np.array_equal(np.asarray(res.std), np.sqrt(np.asarray(res.var)))


# ---------------------------------------------------------------------------
# checkpoint: ensemble layout export/restore
# ---------------------------------------------------------------------------
def test_save_ensemble_restores_into_serve(reg, tmp_path):
    """train -> save_ensemble -> from_checkpoint serves the same statistics
    as serving the live ClusterEngine state."""
    scheds = ensemble_async(WorkerModel(num_workers=4, seed=1), 12, C, seed=0)
    tau = max(s.max_delay for s in scheds)
    sampler = samplers.sgld("consistent", lambda w, b: reg.grad(w, b),
                            gamma=1e-4, sigma=1e-3, tau=max(tau, 1))
    engine = ClusterEngine(sampler, num_chains=C, chunk_size=6,
                           batch_fn=lambda k: reg.sample_batch(k, 32))
    state = engine.init(jnp.zeros(5), jax.random.PRNGKey(3), jitter=0.1)
    state, _ = engine.run(state, steps=12, schedule=scheds,
                          key=jax.random.PRNGKey(4))

    path = str(tmp_path / "bank.npz")
    engine.save_ensemble(state, path)
    live = ServeEngine.from_cluster(state, regression_predict(reg))
    restored = ServeEngine.from_checkpoint(path, like=jnp.zeros(5),
                                           predict_fn=regression_predict(reg))
    assert restored.num_chains == C
    z = jnp.linspace(-1.0, 1.0, 6)
    for got, want in zip(restored(z), live(z)):
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_restore_ensemble_broadcasts_single_model(tmp_path):
    path = str(tmp_path / "single.npz")
    single = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.float32(1.5)}
    save_checkpoint(path, single)
    with pytest.raises(ValueError, match="num_chains"):
        restore_ensemble(path, single)
    bank = restore_ensemble(path, single, num_chains=4)
    assert bank["w"].shape == (4, 2, 3) and bank["b"].shape == (4,)
    assert np.array_equal(np.asarray(bank["w"][2]), np.asarray(single["w"]))


def test_restore_ensemble_rejects_mixed_layout(tmp_path):
    """A checkpoint mixing chain-stacked and single-model leaves (scalar
    leaves included) must raise the documented ValueError, not crash."""
    path = str(tmp_path / "mixed.npz")
    like = {"w": jnp.zeros((2, 3)), "b": jnp.float32(0.0)}
    save_checkpoint(path, {"w": jnp.zeros((C, 2, 3)), "b": jnp.float32(0.0)})
    with pytest.raises(ValueError, match="neither a single-model nor"):
        restore_ensemble(path, like)


def test_non_donating_engine_exact_bucket_passthrough(reg, bank, reference):
    """donate=False serves exact-bucket device requests without the
    donation-shield copy, and the statistics are unchanged."""
    engine = ServeEngine(predict_fn=regression_predict(reg), params=bank,
                         donate=False)
    z = jnp.linspace(-1.0, 1.0, 8)  # exactly bucket 8
    res, ref = engine(z), reference(z)
    for got, want in zip(res, ref):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(z)[-1] == 1.0  # caller's buffer untouched


def test_restore_ensemble_rejects_chain_mismatch(tmp_path):
    path = str(tmp_path / "bank.npz")
    single = {"w": jnp.zeros((2, 3))}
    save_checkpoint(path, {"w": jnp.zeros((C, 2, 3))})
    with pytest.raises(ValueError, match=f"holds {C} chains"):
        restore_ensemble(path, single, num_chains=3)
    assert restore_ensemble(path, single)["w"].shape == (C, 2, 3)


# ---------------------------------------------------------------------------
# model-layer predict fns: the transformer serving path
# ---------------------------------------------------------------------------
def test_transformer_bank_serves_next_token_logits():
    from repro.configs import get_reduced
    from repro.models import transformer_next_token_predict
    from repro.models.transformer import Model, init_params

    cfg = get_reduced("qwen3-4b")
    model = Model(cfg, mesh=None, remat=False)
    chains = 2
    params = jax.vmap(lambda k: init_params(k, cfg))(
        jax.random.split(jax.random.PRNGKey(0), chains))
    predict = transformer_next_token_predict(model)
    engine = ServeEngine(predict_fn=predict, params=params,
                         quantiles=(0.1, 0.9))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 8), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    res = engine({"tokens": tokens})
    assert res.mean.shape == (3, cfg.vocab_size)
    assert res.quantiles.shape == (2, 3, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(res.mean)))

    # Bayesian model averaging: the ensemble mean is the chain average of
    # the per-chain serving-path logits
    per_chain = jax.jit(jax.vmap(predict, in_axes=(0, None)))(
        params, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(res.mean),
                               np.asarray(jnp.mean(per_chain, axis=0)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# sharded serving (subprocess: 8 forced host devices, debug mesh)
# ---------------------------------------------------------------------------
SCRIPT_SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.cluster import ServeEngine
from repro.core import PolyRegression
from repro.launch.mesh import make_debug_mesh
from repro.models import regression_predict

reg = PolyRegression.make(jax.random.PRNGKey(0))
bank = jax.random.normal(jax.random.PRNGKey(1), (8, 5))
predict = regression_predict(reg)

local = ServeEngine(predict_fn=predict, params=bank)
mesh = make_debug_mesh(data=4, model=2)
sharded = ServeEngine(predict_fn=predict, params=bank, mesh=mesh)

rel = 0.0
for i, n in enumerate((5, 3, 16, 8)):
    z = jax.random.uniform(jax.random.PRNGKey(10 + i), (n,),
                           minval=-1.0, maxval=1.0)
    a, b = local(z), sharded(z)
    for x, y in zip(a, b):
        x, y = np.asarray(x), np.asarray(y)
        rel = max(rel, float(np.abs(x - y).max() / np.abs(x).max()))
spec = sharded.params.sharding.spec
print(json.dumps({
    "max_rel_err": rel,
    "chain_axis_sharded": spec[0] == "data",
    "traces": sharded.num_traces,
    "buckets": 3,
}))
"""


@pytest.mark.slow
def test_sharded_serve_bitwise_equal_single_device():
    """Acceptance criterion: chain-sharded predictive mean/var/quantiles
    match the gathered single-device reference, with one trace per shape
    bucket.  The two programs reduce 8 float32 chains in different orders,
    so each statistic may differ by a few ulps: 1e-6 of its largest
    magnitude is 8 summands x 2^-24 with room to spare."""
    from subproc import run_json

    res = run_json(SCRIPT_SHARDED, timeout=600)
    assert res["max_rel_err"] <= 1e-6, res
    assert res["chain_axis_sharded"], res
    assert res["traces"] == res["buckets"], res
