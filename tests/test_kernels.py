"""Pallas kernels vs pure-jnp oracles (interpret mode), hypothesis sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import langevin_update as lu
from repro.kernels.ops import (
    delay_gather_flat,
    fused_delay_gather,
    fused_langevin_update,
)
from repro.kernels.ref import delay_gather_ref, langevin_update_ref
from repro.kernels.rng import normal_from_counter, threefry2x32
from repro.utils import round_up


# ---------------------------------------------------------------------------
# RNG building block
# ---------------------------------------------------------------------------
def test_threefry_reference_vector():
    """Threefry2x32 known-answer test (Random123 test vector, zeros)."""
    x0, x1 = threefry2x32(jnp.uint32(0), jnp.uint32(0),
                          jnp.uint32(0), jnp.uint32(0))
    assert (int(x0), int(x1)) == (0x6B200159, 0x99BA4EFE)


def test_normal_statistics():
    counter = jnp.arange(1 << 18, dtype=jnp.uint32)
    z = np.asarray(normal_from_counter(jnp.uint32(7), jnp.uint32(9), counter))
    assert abs(z.mean()) < 0.01
    assert abs(z.std() - 1.0) < 0.01
    assert abs((z**3).mean()) < 0.03  # skew
    assert abs((z**4).mean() - 3.0) < 0.1  # kurtosis


def test_rng_deterministic_and_seed_sensitive():
    c = jnp.arange(4096, dtype=jnp.uint32)
    a = normal_from_counter(jnp.uint32(1), jnp.uint32(2), c)
    b = normal_from_counter(jnp.uint32(1), jnp.uint32(2), c)
    d = normal_from_counter(jnp.uint32(1), jnp.uint32(3), c)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.abs(np.asarray(a) - np.asarray(d)).max() > 0.1


# ---------------------------------------------------------------------------
# langevin_update kernel
# ---------------------------------------------------------------------------
@given(rows=st.integers(1, 600), cols=st.integers(1, 2100),
       gamma=st.floats(1e-5, 0.5), scale=st.floats(0.0, 1.0))
@settings(max_examples=10, deadline=None)
def test_langevin_kernel_vs_ref(rows, cols, gamma, scale):
    """Any 2-D view, ragged edge blocks in both dimensions included."""
    x = jax.random.normal(jax.random.PRNGKey(rows % 17), (rows, cols))
    g = jax.random.normal(jax.random.PRNGKey(1), (rows, cols))
    seed = jnp.array([cols % 251, 77], jnp.uint32)
    got = lu.langevin_update_2d(x, g, seed, gamma, scale)
    want = langevin_update_ref(x, g, seed, gamma, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# Views that reach each branch of ``lu.tiling``: a block width that divides
# the view (2560 -> 1280, 9728 -> 512, 4096 and 1024 -> 1024, 128), one that
# cannot (999, 1: the masked edge), rows that are neither a multiple of the
# strip nor of the block, and views with fewer rows than a strip.
TILING_VIEWS = [(45, 2560), (40, 9728), (37, 4096), (41, 1024), (300, 128),
                (300, 999), (300, 1), (2, 2560), (2, 128), (1, 1)]


@pytest.mark.parametrize("rows,cols", TILING_VIEWS)
def test_langevin_kernel_bitwise_equals_ref(rows, cols):
    """The kernel's bits are the oracle's, whatever the blocking: the bf16
    update (a leaf's dtype in the sampler), and in fp32 the noise alone,
    which is the stream the benchmark's reference follows."""
    seed = jnp.array([0x9E3779B9, 12345], jnp.uint32)
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, cols), jnp.bfloat16)
    g = jax.random.normal(jax.random.PRNGKey(cols), (rows, cols), jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(lu.langevin_update_2d(x, g, seed, 1e-3, 0.5)),
        np.asarray(langevin_update_ref(x, g, seed, 1e-3, 0.5)))
    zero = jnp.zeros((rows, cols), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(lu.langevin_update_2d(zero, zero, seed, 0.0, 1.0)),
        np.asarray(langevin_update_ref(zero, zero, seed, 0.0, 1.0)))


@pytest.mark.parametrize("rows,cols", TILING_VIEWS + [
    (151936, 2560), (19456, 2560), (5120, 9728), (5120, 4096), (5120, 1024),
    (4096, 1664), (1 << 20, 1)])
def test_langevin_tiling(rows, cols):
    """Blocks divide a lane-aligned width; strips hold at most ``STRIP``
    elements, whole f32 vregs, and divide the block's rows."""
    br, bc, sr = lu.tiling(rows, cols)
    if cols % lu.LANE == 0:
        assert cols % bc == 0 and bc % lu.LANE == 0 and bc <= lu.WIDEST
    else:
        assert bc == min(cols, lu.FALLBACK_COLS)
    assert br % sr == 0 and br <= max(rows, sr)
    if rows > sr:
        assert sr % 8 == 0
        assert sr * round_up(bc, lu.LANE) <= lu.STRIP
    else:
        assert br == sr == rows


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_langevin_kernel_dtypes(dtype):
    n = 3000
    x = jnp.ones((n,), dtype)
    g = jnp.ones((n,), dtype)
    out = lu.langevin_update_2d(x[None], g[None], jnp.array([0, 0], jnp.uint32),
                                0.5, 0.0)
    np.testing.assert_allclose(np.asarray(out, np.float32), 0.5, rtol=1e-2)
    assert out.dtype == dtype


def test_fused_tree_update_noise_statistics():
    params = {"a": jnp.zeros((200, 700)), "b": jnp.zeros((999,))}
    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    out = fused_langevin_update(params, grads, jnp.array([5, 6], jnp.uint32),
                                0.0, 1.0)
    z = np.concatenate([np.asarray(x).ravel() for x in
                        jax.tree_util.tree_leaves(out)])
    assert abs(z.mean()) < 0.02 and abs(z.std() - 1.0) < 0.02
    # distinct leaves get distinct noise
    assert np.abs(np.asarray(out["a"]).ravel()[:999]
                  - np.asarray(out["b"])).max() > 0.1


# ---------------------------------------------------------------------------
# delay_gather kernel
# ---------------------------------------------------------------------------
@given(depth=st.integers(1, 9), n=st.integers(1, 20_000), seed=st.integers(0, 99))
@settings(max_examples=15, deadline=None)
def test_delay_gather_vs_ref(depth, n, seed):
    h = jax.random.normal(jax.random.PRNGKey(seed), (depth, n))
    slots = jax.random.randint(jax.random.PRNGKey(seed + 1), (n,), 0, depth)
    got = delay_gather_flat(h, slots)
    want = delay_gather_ref(h, slots)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_delay_gather_dtypes(dtype):
    h = jnp.arange(4 * 5000).reshape(4, 5000).astype(dtype)
    slots = jnp.tile(jnp.arange(4, dtype=jnp.int32), 1250)
    got = delay_gather_flat(h, slots)
    want = delay_gather_ref(h, slots)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_delay_gather_matches_ring_semantics():
    from repro.core import init_ring, push, read_inconsistent

    params = {"w": jnp.zeros((64, 33))}
    ring = init_ring(params, tau=3)
    for k in range(1, 6):
        ring = push(ring, {"w": jnp.full((64, 33), float(k))})
    delays = {"w": jax.random.randint(jax.random.PRNGKey(0), (64, 33), 0, 4)}
    want = read_inconsistent(ring, delays)
    got = fused_delay_gather(ring.history, delays, ring.head, ring.depth)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(want["w"]))
