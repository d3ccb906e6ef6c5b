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


@pytest.mark.parametrize("shape", [
    (VOCAB, D), (2 * D, H * HD), (2, D), (2 * FF, D), (2 * D, FF),
    (2 * D, KV * HD), (2, HD)],
    ids=["embed", "stacked_wq", "norms", "w_down", "w_gate", "wk", "q_norm"])
def test_langevin_update_compiles(one_chip, shape):
    """The fused update over each of qwen3-4b's leaf views (two layers
    stacked), in the leaf's dtype (bf16): blocks as wide as divides the
    view, a ragged last row block, and views narrower than a strip.  The
    call keeps the operands the roofline metric finds it by."""
    x = _on(one_chip, jax.ShapeDtypeStruct(shape, jnp.bfloat16))
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
