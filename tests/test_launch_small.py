"""Launch-stack integration at CI scale: a 2x4 debug mesh in a subprocess
(8 forced host devices) exercises param_structs -> lower -> compile ->
roofline for a reduced arch, train + decode."""

import os

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
from dataclasses import replace
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_reduced, ShapeConfig
from repro.launch.steps import (build_model, param_structs, batch_specs,
                                cache_spec_tree, make_sgld_train_step,
                                make_decode_step)
from repro.launch import roofline as rl
from repro.launch.jaxpr_cost import step_cost

from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(data=2, model=4)
shape = ShapeConfig("ci", seq_len=64, global_batch=4, kind="train",
                    num_microbatches=2)
cfg0 = replace(get_reduced("qwen3-4b"), num_heads=8, num_kv_heads=2)
model, cfg, baxes, faxes = build_model(cfg0, shape, mesh, opts=("attn_shard",))
pstructs, pshard = param_structs(cfg, mesh, faxes)
bstructs = batch_specs(cfg, shape, mesh, baxes)
rep = NamedSharding(mesh, P())
out = {}
with jax.set_mesh(mesh):
    step = make_sgld_train_step(model, shape)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    compiled = jax.jit(step, out_shardings=(pshard, rep)).lower(
        pstructs, bstructs, key).compile()
    cost = step_cost(step, pstructs, bstructs, key, num_devices=8)
    roof = rl.analyze("ci/train", compiled, 8, rl.model_flops(cfg, shape),
                      jaxpr_cost=cost)
    out["train"] = {"dominant": roof.dominant,
                    "flops": roof.flops_per_device,
                    "coll": roof.collective_bytes_per_device}
    # decode
    dshape = ShapeConfig("ci_dec", seq_len=64, global_batch=4, kind="decode")
    model2, cfg2, baxes2, _ = build_model(cfg0, dshape, mesh)
    cstructs, cshard = cache_spec_tree(model2, cfg2, dshape, mesh, baxes2)
    bst = batch_specs(cfg2, dshape, mesh, baxes2, kind="decode")
    dstep = make_decode_step(model2)
    c2 = jax.jit(dstep, out_shardings=(None, cshard)).lower(
        pstructs, cstructs, bst).compile()
    out["decode_ok"] = True
print(json.dumps(out))
"""


@pytest.mark.slow
def test_small_mesh_launch_stack():
    from subproc import run_json

    out = run_json(SCRIPT, timeout=600)
    assert out["decode_ok"]
    assert out["train"]["flops"] > 0
    assert out["train"]["dominant"] in ("compute", "memory", "collective")
