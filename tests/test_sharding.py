"""Sharding integration: runs in a SUBPROCESS with 8 forced host devices so
the main pytest process keeps seeing 1 device (per the dry-run isolation
rule).  Verifies that the sharded MoE path equals the local path and that a
small mesh train step lowers, compiles, and executes."""

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
from repro.configs import get_reduced, ShapeConfig
from repro.data import make_batch
from repro.models.common import partition_tree
from repro.models.transformer import Model, init_params
from repro.launch.steps import make_sgld_train_step, sanitized_named

from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(data=2, model=4)
cfg = replace(get_reduced("qwen3-4b"), dtype="float32")
shape = ShapeConfig("t", seq_len=64, global_batch=4, kind="train",
                    num_microbatches=2)
model = Model(cfg, mesh=mesh, batch_axes=("data",))
params = init_params(jax.random.PRNGKey(0), cfg)
specs = partition_tree(params, cfg.param_sharding, cfg=cfg,
                       model_size=mesh.shape["model"])
pshard = sanitized_named(mesh, specs, params)
params = jax.device_put(params, pshard)
batch = make_batch(cfg, shape, jax.random.PRNGKey(1), "train")
step = make_sgld_train_step(model, shape, mode="sync", gamma=1e-3, sigma=1e-8)
with jax.set_mesh(mesh):
    jstep = jax.jit(step, out_shardings=(pshard, NamedSharding(mesh, P())))
    new_params, loss = jstep(params, batch, jnp.array([0, 1], jnp.uint32))
    loss2 = None
    # unsharded reference
model0 = Model(cfg, mesh=None)
step0 = make_sgld_train_step(model0, shape, mode="sync", gamma=1e-3, sigma=1e-8)
_, loss_ref = jax.jit(step0)(jax.device_get(params), batch,
                             jnp.array([0, 1], jnp.uint32))
print(json.dumps({"loss": float(loss), "loss_ref": float(loss_ref),
                  "finite": bool(np.isfinite(float(loss)))}))
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


@pytest.mark.slow
def test_sharded_train_step_matches_unsharded_loss():
    res = _run(SCRIPT_TRAIN)
    assert res["finite"], res
    assert abs(res["loss"] - res["loss_ref"]) < 5e-3, res
