"""The kernels against their jnp oracles, at fixed shapes.

``test_kernels.py`` sweeps the sampling kernels' checks with hypothesis;
these cases run without it.  The decode kernels are checked here against
the dense oracles, which share no code with them (the blocked oracles are
pinned bitwise in ``test_decode.py`` and ``test_paged.py``).  The kernels
run in the Pallas interpreter here, the same body the chip compiles
(``tests/test_tpu_compile.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_step as ds
from repro.kernels import delay_gather as dg
from repro.kernels import langevin_update as lu
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.ref import (
    decode_step_dense_ref,
    delay_gather_ref,
    grouped_matmul_ref,
    langevin_update_ref,
    paged_decode_step_dense_ref,
)
from repro.kernels.rng import threefry2x32

# fp32 inputs: the kernels and the dense oracles differ only in the order
# of fp32 sums (online vs one softmax, normalizing after vs before the
# value sum), a few ulps of outputs of order one
DENSE_ATOL = 1e-5


@pytest.mark.parametrize("dtype", [jnp.uint32, jnp.int32])
def test_threefry_known_answer_in_either_word_type(dtype):
    """Random123's zero vector, and the same bits from int32 words (the
    kernel's) as from uint32 words over a range of counters."""
    z = jnp.zeros((), dtype)
    x0, x1 = threefry2x32(z, z, z, z)
    assert (int(x0) & 0xFFFFFFFF, int(x1) & 0xFFFFFFFF) == (0x6B200159,
                                                           0x99BA4EFE)
    c = jnp.arange(1 << 12, dtype=jnp.uint32) * jnp.uint32(0x9E3779B9)
    want = threefry2x32(jnp.uint32(7), jnp.uint32(0xDEADBEEF), c, ~c)
    words = jax.lax.bitcast_convert_type(c, dtype)
    got = threefry2x32(jax.lax.bitcast_convert_type(jnp.uint32(7), dtype),
                       jax.lax.bitcast_convert_type(jnp.uint32(0xDEADBEEF),
                                                    dtype),
                       words, ~words)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(
            np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint32)),
            np.asarray(b))


@pytest.mark.parametrize("shape", [(300, 1100), (1, 999), (8, 2560)],
                         ids=["ragged_blocks", "one_row", "narrow"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_langevin_update_matches_oracle(shape, dtype):
    """Kernel == oracle: the noise of element (r, c) is counter r*C + c
    however the grid blocks the view, fp32 arithmetic, one rounding to the
    leaf dtype (so a bf16 result may differ by that one rounding)."""
    x = jax.random.normal(jax.random.PRNGKey(0), shape).astype(dtype)
    g = jax.random.normal(jax.random.PRNGKey(1), shape).astype(dtype)
    seed = jnp.array([3, 0x85EBCA6B], jnp.uint32)
    got = lu.langevin_update_2d(x, g, seed, 0.05, 0.3)
    want = langevin_update_ref(x, g, seed, 0.05, 0.3)
    assert got.dtype == dtype
    tol = 1e-6 if dtype == jnp.float32 else 2.0**-8
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_delay_gather_matches_oracle(dtype):
    """The W-Icon read of a depth-3 ring over two blocks."""
    n = 2 * dg.BLOCK
    h = jax.random.normal(jax.random.PRNGKey(2), (3, n)).astype(dtype)
    slots = jax.random.randint(jax.random.PRNGKey(3), (n,), 0, 3)
    np.testing.assert_array_equal(np.asarray(dg.delay_gather_1d(h, slots)),
                                  np.asarray(delay_gather_ref(h, slots)))


def _normal(key, shapes):
    return [jax.random.normal(k, s, jnp.float32)
            for k, s in zip(jax.random.split(jax.random.PRNGKey(key),
                                             len(shapes)), shapes)]


@pytest.mark.parametrize("smax, n_valid, slot", [(12, 7, 6), (1024, 900, 700)],
                         ids=["one_block", "two_blocks"])
def test_decode_step_matches_dense_softmax(smax, n_valid, slot):
    """The contiguous decode step against one dense fp32 softmax: the head
    mask, the online rescale and the final normalization, across KV
    blocks when smax > BLOCK_KV."""
    B, KV, G, hd = 2, 2, 3, 16
    q, kn, vn, kc, vc = _normal(0, [(B, KV, G, hd), (B, KV, hd), (B, KV, hd),
                                    (B, smax, KV, hd), (B, smax, KV, hd)])
    valid = (jnp.arange(smax) < n_valid).astype(jnp.int32)
    got = ds.decode_step_2d(q, kn, vn, kc, vc, valid, jnp.int32(slot))
    want = decode_step_dense_ref(q, kn, vn, kc, vc, valid, slot)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=0, atol=DENSE_ATOL)
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("maxp", [4, 64], ids=["one_block", "two_blocks"])
def test_paged_decode_step_matches_dense_softmax(maxp):
    """The paged decode step against a dense fp32 softmax over each slot's
    pages gathered in logical order from a permuted pool, positions in the
    first page, mid-table and at the last row."""
    S, KV, G, hd, ps = 3, 2, 3, 16, 16
    n_pages = S * maxp + 1
    q, kn, vn, kp, vp = _normal(1, [(S, KV, G, hd), (S, KV, hd), (S, KV, hd),
                                    (n_pages, ps, KV, hd),
                                    (n_pages, ps, KV, hd)])
    perm = jax.random.permutation(jax.random.PRNGKey(2), n_pages - 1) + 1
    tables = perm.reshape(S, maxp).astype(jnp.int32)
    rows = maxp * ps
    pos = jnp.array([5, rows // 2 + 3, rows - 1], jnp.int32)
    got = ds.paged_decode_step(q, kn, vn, kp, vp, tables, pos)
    want = paged_decode_step_dense_ref(q, kn, vn, kp, vp, tables, pos)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=0, atol=DENSE_ATOL)
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_paged_decode_step_refuses_per_chain_tables():
    """Page tables and positions are shared by every chain of a vmapped
    call; a chain axis on them is an error, not a slower path."""
    S, KV, G, hd, ps, maxp = 2, 1, 1, 8, 4, 2
    q, kn, vn, kp, vp = _normal(3, [(2, S, KV, G, hd), (2, S, KV, hd),
                                    (2, S, KV, hd), (2, 5, ps, KV, hd),
                                    (2, 5, ps, KV, hd)])
    tables = jnp.arange(1, 5, dtype=jnp.int32).reshape(S, maxp)
    pos = jnp.zeros((S,), jnp.int32)
    with pytest.raises(ValueError, match="shared by every chain"):
        jax.vmap(ds.paged_decode_step)(q, kn, vn, kp, vp,
                                       jnp.stack([tables, tables]),
                                       jnp.stack([pos, pos]))


@pytest.mark.parametrize("sizes", [[10, 0, 30], [96, 0, 0], [0, 0, 0]],
                         ids=["ragged", "one_group", "none_held"])
def test_grouped_matmul_matches_ragged_dot(sizes):
    """Forward and both gradients against XLA's ragged_dot, fp32: each
    group's rows through its own matrix, rows past the groups zero (and no
    gradient from them), an empty group anywhere; 96 rows, not a multiple
    of the kernel's 512-row tile."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs = jax.random.normal(k1, (96, 64))
    rhs = jax.random.normal(k2, (3, 64, 40))
    cot = jax.random.normal(k3, (96, 40))
    sizes = jnp.asarray(sizes, jnp.int32)

    def pull(f):
        out, vjp = jax.vjp(lambda a, b: f(a, b, sizes), lhs, rhs)
        return (out,) + vjp(cot)

    got, want = pull(grouped_matmul), pull(grouped_matmul_ref)
    for a, b in zip(got, want):
        # the same fp32 products summed in another order
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=DENSE_ATOL * 8)
    assert not np.asarray(got[0])[int(sizes.sum()):].any()
