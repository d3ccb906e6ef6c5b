"""Sharding integration: runs in a SUBPROCESS with 8 forced host devices so
the main pytest process keeps seeing 1 device (the forced devices stay
isolated).  Verifies that the sharded MoE path equals the local path and that
``samplers.sgld`` over a microbatched gradient, its parameters sharded on a
2x4 mesh, commits the losses the unsharded chain commits."""

import functools
import os

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT_MOE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
from dataclasses import replace
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_reduced
from repro.models.moe import apply_moe, init_moe

from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(data=2, model=4)
cfg = replace(get_reduced("phi3.5-moe-42b-a6.6b"), dtype="float32",
              num_experts=8, experts_per_token=2)
p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

# dropless on both paths: the shards route their own tokens to their own
# experts with the same local computation, and nothing is dropped
y_local, aux_local, load_local = apply_moe(p, x, cfg, mesh=None)
with jax.set_mesh(mesh):
    y_shard, aux_shard, load_shard = jax.jit(
        lambda p, x: apply_moe(p, x, cfg, mesh=mesh, batch_axes=("data",)))(p, x)
err = float(jnp.abs(y_local - y_shard).max())
rel = err / float(jnp.abs(y_local).max())
print(json.dumps({"rel_err": rel,
                  "aux_err": abs(float(aux_local) - float(aux_shard)),
                  "loads": [np.asarray(load_local).tolist(),
                            np.asarray(load_shard).tolist()],
                  "finite": bool(np.isfinite(np.asarray(y_shard)).all())}))
"""

SCRIPT_TRAIN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
from dataclasses import replace
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import samplers
from repro.configs import get_reduced, ShapeConfig
from repro.data import make_batch
from repro.models.common import partition_tree
from repro.models.transformer import Model, init_params
from repro.train.loop import make_grad_fn

from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(data=2, model=4)
cfg = replace(get_reduced("qwen3-4b"), dtype="float32")
shape = ShapeConfig("t", seq_len=64, global_batch=4, kind="train")
params = init_params(jax.random.PRNGKey(0), cfg)
specs = partition_tree(params, cfg.param_sharding, cfg=cfg,
                       model_size=mesh.shape["model"])
pshard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                is_leaf=lambda x: isinstance(x, P))
sharded = jax.device_put(params, pshard)
batches = [make_batch(cfg, shape, jax.random.PRNGKey(1 + i), "train")
           for i in range(3)]
delays = [0, 1, 2]


def losses(mode, model, params):
    # two microbatches of two sequences: the accumulated oracle
    sampler = samplers.sgld(mode, make_grad_fn(model, 2), has_aux=True,
                            gamma=1e-3, sigma=1e-8,
                            tau=2 if mode == "consistent" else 0)
    step = jax.jit(sampler.step)
    state = sampler.init(params, jax.random.PRNGKey(2))
    out = []
    for batch, delay in zip(batches, delays):
        state, aux = step(state, batch, delay)
        out.append(float(aux["loss"]))
    return out, bool(all(np.isfinite(np.asarray(x)).all()
                         for x in jax.tree_util.tree_leaves(state.params)))


res = {}
for mode in ("sync", "pipeline", "consistent"):
    with jax.set_mesh(mesh):
        loss, finite = losses(mode, Model(cfg, mesh=mesh, batch_axes=("data",)),
                              sharded)
    loss_ref, _ = losses(mode, Model(cfg, mesh=None), params)
    res[mode] = {"loss": loss, "loss_ref": loss_ref, "finite": finite}
print(json.dumps(res))
"""


def _run(script: str) -> dict:
    from subproc import run_json

    return run_json(script, timeout=600)


@pytest.mark.slow
def test_sharded_moe_matches_local():
    res = _run(SCRIPT_MOE)
    assert res["rel_err"] < 5e-5, res
    # aux is computed per data shard then averaged (standard practice);
    # it differs from the global statistic by O(shard-variance)
    assert res["aux_err"] < 0.1, res
    assert res["finite"], res
    # every (token, expert) pair counted once: 4 x 16 tokens, 2 experts each
    assert res["loads"][0] == res["loads"][1], res
    assert sum(res["loads"][0]) == 4 * 16 * 2, res


@functools.lru_cache(maxsize=None)
def _train_results() -> dict:
    """One subprocess for every mode: the sharded chains' per-commit losses
    beside the unsharded ones."""
    return _run(SCRIPT_TRAIN)


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["sync", "pipeline", "consistent"])
def test_sharded_train_step_matches_unsharded_loss(mode):
    res = _train_results()[mode]
    assert res["finite"], res
    assert len(res["loss"]) == len(res["loss_ref"]) == 3, res
    for loss, ref in zip(res["loss"], res["loss_ref"]):
        assert abs(loss - ref) < 5e-3, res
