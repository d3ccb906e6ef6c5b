"""Compiles for a described TPU v5e chip, with no chip attached.

The Pallas kernels of the main path at qwen3-4b widths, and the sampling
chunks ``chip_smoke.py`` runs on one chip and on four, go through the chip's own compiler: what it
refuses (an untiled block, a scalar outside SMEM, too much VMEM, a program
larger than the chip's memory) fails here.  Each compiled program must
hold the kernel as a ``tpu_custom_call``, not the Pallas interpreter.
Nothing runs, so these tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a test worker that is not given this
file must not take it.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_step as ds
from repro.kernels import delay_gather as dg
from repro.kernels import langevin_update as lu
from repro.kernels.grouped_matmul import grouped_matmul

ROOT = os.path.join(os.path.dirname(__file__), "..")
# qwen3-4b widths
D, VOCAB, H, KV, HD, FF = 2560, 151936, 32, 8, 128, 9728


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Shape-dtype structs of ``tree`` placed on ``sharding``."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# The fused update's call as the benchmark's roofline metric finds it in a
# trace: a Pallas custom call whose scalar-prefetch operands are the s32[2]
# seed, then the f32[2] (gamma, scale).  Copied from ``KERNEL`` in
# benchmarks/chip/metrics/langevin_update_roofline.py.
LANGEVIN_CALL = (r'custom-call\(s32\[2\]\{[^}]*\} [^,]+, f32\[2\]\{.*'
                 r'custom_call_target="tpu_custom_call"')


def _text_with_operand_shapes(compiled) -> str:
    """The compiled program's text with each operand's shape before its
    name, as a TPU trace names an operation (``as_text()`` prints the
    operands' names alone)."""
    from jax._src.lib import xla_client

    options = xla_client._xla.HloPrintOptions()
    options.print_operand_shape = True
    return "\n".join(m.to_string(options)
                     for m in compiled.runtime_executable().hlo_modules())


# moonlight-16b-a3b's cut (benchmarks/chip/configs): 4 MoE layers of 8 held
# experts 2048 x 1408, MLA's kv_a 2048 x 576, the router's 64 outputs
MD, MF, MOE_LAYERS, HELD = 2048, 1408, 4, 8


@pytest.mark.parametrize("shape,dtype", [
    ((VOCAB, D), jnp.bfloat16), ((2 * D, H * HD), jnp.bfloat16),
    ((2, D), jnp.bfloat16), ((2 * FF, D), jnp.bfloat16),
    ((2 * D, FF), jnp.bfloat16), ((2 * D, KV * HD), jnp.bfloat16),
    ((2, HD), jnp.bfloat16),
    ((MOE_LAYERS * HELD * MD, MF), jnp.bfloat16),
    ((MOE_LAYERS * HELD * MF, MD), jnp.bfloat16),
    ((MOE_LAYERS * MD, 576), jnp.bfloat16),
    ((MOE_LAYERS * MD, 64), jnp.float32), ((MOE_LAYERS, 512), jnp.float32)],
    ids=["embed", "stacked_wq", "norms", "w_down", "w_gate", "wk", "q_norm",
         "experts_w_gate", "experts_w_down", "wkv_a", "router", "kv_norm"])
def test_langevin_update_compiles(one_chip, shape, dtype):
    """The fused update over each of qwen3-4b's leaf views (two layers
    stacked) and over the views moonlight-16b-a3b adds (3-D expert leaves
    1408 wide, MLA's 576-wide kv_a and the 64-wide float32 router, which
    are no multiple of 128 lanes and take the fallback tiling), in the
    leaf's dtype: blocks as wide as divides the view, a ragged last row
    block, and views narrower than a strip.  The call keeps the operands
    the roofline metric finds it by."""
    x = _on(one_chip, jax.ShapeDtypeStruct(shape, dtype))
    seed = _on(one_chip, jax.ShapeDtypeStruct((2,), jnp.uint32))
    compiled = _compile(
        lambda x, g, s: lu.langevin_update_2d(x, g, s, 1e-3, 1e-2),
        x, x, seed)
    assert re.search(LANGEVIN_CALL, _text_with_operand_shapes(compiled))


def test_langevin_update_kernel_is_named(one_chip):
    """The kernel carries its own name: the Pallas call's ``kernel_name``
    in the lowered program, and the compiled custom call's instruction
    name, which a TPU trace shows as the operation's name."""
    x = _on(one_chip, jax.ShapeDtypeStruct((2, D), jnp.bfloat16))
    seed = _on(one_chip, jax.ShapeDtypeStruct((2,), jnp.uint32))
    lowered = jax.jit(
        lambda x, g, s: lu.langevin_update_2d(x, g, s, 1e-3, 1e-2)
    ).lower(x, x, seed)
    assert 'kernel_name = "langevin_update"' in lowered.as_text()
    assert re.search(r'%langevin_update(\.\d+)? = [^\n]*'
                     r'custom_call_target="tpu_custom_call"',
                     lowered.compile().as_text())


# The grouped matmul's calls as ``moe_gmm_roofline`` finds them in a trace:
# megablox ``gmm``/``tgmm`` custom calls whose operands open with the s32[]
# tile count, three s32 group-metadata vectors and the s32[1] group offset.
# Copied from ``KERNEL`` in benchmarks/chip/metrics/moe_gmm_roofline.py.
GMM_CALL = (r'custom-call\(s32\[\]\{[^}]*\} [^,]+, '
            r'(s32\[\d+\]\{[^}]*\} [^,]+, ){3}s32\[1\]\{[^}]*\} .*'
            r'custom_call_target="tpu_custom_call"')


def test_grouped_matmul_compiles(one_chip):
    """The held experts' SwiGLU through the grouped matmul, forward and
    backward, at moonlight-16b-a3b.sample-8k's buffer (2 x 8192 tokens x 6
    routed rows) for 8 experts: every call is a ``gmm`` or ``tgmm`` kernel
    the roofline metric's pattern finds, and the fused update's does not."""
    rows = 2 * 8192 * 6
    bf = jnp.bfloat16
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((rows, MD), bf),
        jax.ShapeDtypeStruct((HELD, MD, MF), bf),
        jax.ShapeDtypeStruct((HELD, MD, MF), bf),
        jax.ShapeDtypeStruct((HELD, MF, MD), bf),
        jax.ShapeDtypeStruct((HELD,), jnp.int32)))

    def swiglu(x, wg, wu, wd, sizes):
        h = jax.nn.silu(grouped_matmul(x, wg, sizes)) * \
            grouped_matmul(x, wu, sizes)
        return jnp.sum(grouped_matmul(h, wd, sizes).astype(jnp.float32))

    compiled = _compile(jax.grad(swiglu, argnums=(0, 1, 2, 3)), *args)
    calls = [line for line in _text_with_operand_shapes(compiled).split("\n")
             if 'custom_call_target="tpu_custom_call"' in line]
    names = sorted(re.sub(r"\.\d+$", "", line.split(" = ")[0].strip().lstrip("%"))
                   for line in calls)
    # the forward's gate and up (the down projection's output is unused
    # here), the three input gradients and the three weight gradients
    assert names == ["gmm"] * 5 + ["tgmm"] * 3, names
    assert all(re.search(GMM_CALL, line) for line in calls)
    assert not any(re.search(LANGEVIN_CALL, line) for line in calls)


def test_delay_gather_compiles(one_chip):
    """The W-Icon read of one layer's wq from a tau=2 ring (depth 3)."""
    n = D * H * HD
    hist = _on(one_chip, jax.ShapeDtypeStruct((3, n), jnp.bfloat16))
    slots = _on(one_chip, jax.ShapeDtypeStruct((n,), jnp.int32))
    _compile(dg.delay_gather_1d, hist, slots)


def test_decode_step_compiles_chain_batched(one_chip):
    """The contiguous decode step at smax 4096, batched over 2 chains the
    way the decode engine vmaps it."""
    C, B, smax = 2, 8, 4096
    bf = jnp.bfloat16
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((C, B, KV, H // KV, HD), bf),
        jax.ShapeDtypeStruct((C, B, KV, HD), bf),
        jax.ShapeDtypeStruct((C, B, KV, HD), bf),
        jax.ShapeDtypeStruct((C, B, smax, KV, HD), bf),
        jax.ShapeDtypeStruct((C, B, smax, KV, HD), bf),
        jax.ShapeDtypeStruct((smax,), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32)))
    _compile(jax.vmap(ds.decode_step_2d, in_axes=(0,) * 5 + (None, None)),
             *args)


def test_paged_decode_step_compiles_chain_batched(one_chip):
    """The paged decode step at page 16 and max_seq 4096, pools batched
    over 2 chains, page tables and positions shared."""
    C, S, page, maxp = 2, 8, 16, 4096 // 16
    n_pages = S * maxp + 1
    bf = jnp.bfloat16
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((C, S, KV, H // KV, HD), bf),
        jax.ShapeDtypeStruct((C, S, KV, HD), bf),
        jax.ShapeDtypeStruct((C, S, KV, HD), bf),
        jax.ShapeDtypeStruct((C, n_pages, page, KV, HD), bf),
        jax.ShapeDtypeStruct((C, n_pages, page, KV, HD), bf),
        jax.ShapeDtypeStruct((S, maxp), jnp.int32),
        jax.ShapeDtypeStruct((S,), jnp.int32)))
    _compile(jax.vmap(ds.paged_decode_step, in_axes=(0,) * 5 + (None, None)),
             *args)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _require_fits(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert need < 16 * 2**30, ma


def test_smoke_sampling_chunk_fits_one_chip(one_chip, smoke):
    """The chunk ``chip_smoke.py`` runs (qwen3-4b widths, its depth, chains
    and tau) compiles for one chip with the fused update as a kernel, and
    its arguments, outputs and temporaries fit the chip's 16 GiB."""
    cfg = smoke.smoke_config()
    _, engine = smoke.sampling_engine(cfg, 1)
    args = _on(one_chip, smoke.chunk_shapes(cfg, engine, 1))
    _require_fits(engine.lower_chunk(*args).compile())


def test_smoke_sharded_sampling_chunk_fits_four_chips(topo, smoke):
    """The ``shard_map`` chunk ``chip_smoke.py --chips 4`` runs, one chain
    per chip of the 2x2 host, keeps the fused update a kernel, and each
    chip's share fits its 16 GiB."""
    from jax.sharding import AxisType

    mesh = jax.make_mesh((4,), ("data",), devices=topo.devices,
                         axis_types=(AxisType.Auto,))
    cfg = smoke.smoke_config()
    _, engine = smoke.sampling_engine(cfg, 4, mesh)
    _require_fits(engine.lower_chunk(
        *smoke.chunk_shapes(cfg, engine, 4, mesh)).compile())


def test_moonlight_sampling_chunk_fits_one_chip(one_chip):
    """The chunk of ``moonlight-16b-a3b.sample-8k`` (the dense layer and 4
    MoE layers at published widths, 8 of 64 experts held, a 20,480-token
    vocabulary slice; 4 commits of 2 x 8192 tokens, tau 2, fused update)
    compiles for one chip with the grouped matmul and the update as
    kernels, and fits the chip's 16 GiB."""
    from dataclasses import replace

    from repro import samplers
    from repro.cluster import ClusterEngine
    from repro.configs import get_arch
    from repro.models.transformer import Model, init_params
    from repro.train.loop import make_grad_fn

    cfg = replace(get_arch("moonlight-16b-a3b"), num_layers=1 + MOE_LAYERS,
                  experts_held=HELD, vocab_size=20480)
    sampler = samplers.sgld("consistent", make_grad_fn(Model(cfg)),
                            has_aux=True, tau=2, fused=True, gamma=0.1,
                            sigma=1e-6)
    engine = ClusterEngine(sampler, num_chains=1, chunk_size=4,
                           per_chain_batches=True, collect_aux=True)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: init_params(k, cfg), key)
    state = jax.eval_shape(engine.init, params, key)
    batches = {"tokens": jax.ShapeDtypeStruct((4, 1, 2, 8193), jnp.int32)}
    extra = {"rv": jax.ShapeDtypeStruct((4, 1), jnp.int32)}
    compiled = engine.lower_chunk(*_on(one_chip, (state, batches,
                                                  extra))).compile()
    _require_fits(compiled)
    text = compiled.as_text()
    assert re.search(r"%t?gmm(\.\d+)? = ", text)
    assert re.search(r"%langevin_update(\.\d+)? = ", text)
