"""Flash attention vs naive oracle: forward, backward, windows, GQA, latent
widths, the block plan, decode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention
from repro.models.attention import (
    _block_plan,
    _row_ranges,
    decode_attention,
    flash_attention,
    naive_attention,
)
from repro.obs.metrics import registry


def _qkv(key, B, S, H, KV, hd, hv=None):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (B, S, H, hd)),
            jax.random.normal(ks[1], (B, S, KV, hd)),
            jax.random.normal(ks[2], (B, S, KV, hv or hd)))


def _loss(att, t):
    return lambda q, k, v: jnp.sum(att(q, k, v) * t)


def _assert_grads_close(g1, g2):
    for a, b in zip(g1, g2):
        scale = np.abs(np.asarray(a)).max() + 1e-6
        np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(b) / scale,
                                   atol=5e-5)


@pytest.mark.parametrize("gqa", [1, 2, 4])
@pytest.mark.parametrize("window", [None, 32, 64])
def test_flash_forward_matches_naive(window, gqa):
    B, S, KV, hd = 2, 128, 2, 16
    H = KV * gqa
    q, k, v = _qkv(jax.random.PRNGKey(7 * gqa + (window or 0)), B, S, H, KV,
                   hd)
    o1 = naive_attention(q, k, v, causal=True, window=window)
    o2 = flash_attention(q, k, v, True, window, 32, 32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("window", [None, 64])
def test_flash_gradients_match_naive(window):
    B, S, H, KV, hd = 2, 256, 8, 2, 32
    q, k, v = _qkv(jax.random.PRNGKey(0), B, S, H, KV, hd)
    t = jax.random.normal(jax.random.PRNGKey(9), (B, S, H, hd))
    f_naive = _loss(lambda q, k, v: naive_attention(
        q, k, v, causal=True, window=window), t)
    f_flash = _loss(lambda q, k, v: flash_attention(
        q, k, v, True, window, 64, 64), t)
    _assert_grads_close(jax.grad(f_naive, argnums=(0, 1, 2))(q, k, v),
                        jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v))


# (S, q_chunk, k_chunk, H, KV, hd, hv, causal, window)
PLAN_CASES = {
    "nq2": (64, 32, 32, 4, 2, 16, 16, True, None),
    "nq3": (96, 32, 32, 4, 2, 16, 16, True, None),
    "nq16": (512, 32, 32, 2, 2, 16, 16, True, None),
    "gqa4": (128, 32, 32, 8, 2, 16, 16, True, None),
    "mla_192_128": (128, 32, 32, 4, 4, 192, 128, True, None),
    "q64_k32": (128, 64, 32, 4, 2, 16, 16, True, None),
    "q32_k64": (128, 32, 64, 4, 2, 16, 16, True, None),
    "window7": (256, 32, 32, 4, 2, 16, 16, True, 7),
    "window40": (256, 32, 32, 4, 2, 16, 16, True, 40),
    "window64_q64_k32": (256, 64, 32, 4, 2, 16, 16, True, 64),
    "full": (96, 32, 32, 4, 2, 16, 16, False, None),
    "full_window40": (128, 32, 32, 4, 2, 16, 16, False, 40),
}


@pytest.mark.parametrize("case", list(PLAN_CASES), ids=list(PLAN_CASES))
def test_flash_block_plan_matches_naive(case):
    """Forward and gradients over only the visible blocks equal the naive
    softmax over the whole masked square."""
    S, qc, kc, H, KV, hd, hv, causal, window = PLAN_CASES[case]
    q, k, v = _qkv(jax.random.PRNGKey(S + hd), 2, S, H, KV, hd, hv)
    t = jax.random.normal(jax.random.PRNGKey(9), (2, S, H, hv))

    def naive(q, k, v):
        return naive_attention(q, k, v, causal=causal, window=window)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal, window, qc, kc)

    np.testing.assert_allclose(np.asarray(naive(q, k, v)),
                               np.asarray(flash(q, k, v)),
                               atol=2e-5, rtol=1e-4)
    _assert_grads_close(
        jax.grad(_loss(naive, t), argnums=(0, 1, 2))(q, k, v),
        jax.grad(_loss(flash, t), argnums=(0, 1, 2))(q, k, v))


@pytest.mark.parametrize("args,pairs", [
    ((8192, 8192, 512, 512, True, None), 136),
    ((1024, 1024, 512, 512, True, None), 3),
    ((8192, 8192, 512, 512, False, None), 256),
    ((96, 96, 32, 32, True, 20), 5),
    ((128, 128, 64, 32, True, None), 6),
])
def test_block_plan_counts_visible_blocks(args, pairs):
    """The plan keeps exactly the blocks with an attended pair and reports
    their share in the gauge."""
    Sq, Sk, qc, kc, causal, window = args
    plan = _block_plan(*args)
    assert plan.sum() == pairs
    q_pos = np.arange(Sq)[:, None]
    k_pos = np.arange(Sk)[None, :]
    mask = np.ones((Sq, Sk), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    want = mask.reshape(Sq // qc, qc, Sk // kc, kc).any(axis=(1, 3))
    np.testing.assert_array_equal(plan, want)
    share = registry().gauge("attention.block_share").value
    assert share == pairs / plan.size


def test_row_ranges_are_contiguous_runs():
    """Each row's visible k chunks as one [lo, hi) run; an empty row gets
    lo == hi."""
    lo, hi = _row_ranges(_block_plan(96, 96, 32, 32, True, None))
    assert lo.tolist() == [0, 0, 0] and hi.tolist() == [1, 2, 3]
    lo, hi = _row_ranges(_block_plan(128, 128, 32, 32, True, 20))
    assert lo.tolist() == [0, 0, 1, 2] and hi.tolist() == [1, 2, 3, 4]
    lo, hi = _row_ranges(np.array([[False, False], [False, True]]))
    assert lo.tolist() == [0, 1] and hi.tolist() == [0, 2]


@pytest.mark.parametrize("causal,steps", [(True, 136), (False, 256)])
def test_flash_grad_program_skips_masked_blocks(causal, steps, monkeypatch):
    """At 16 x 16 blocks the forward and the backward each compute the
    136 causally visible blocks, not all 256: every block builds its mask
    once, counted over an eager run of ``jax.grad``."""
    calls = []

    def counted(*args):
        calls.append(1)
        return mask_block(*args)

    mask_block = attention._mask_block
    monkeypatch.setattr(attention, "_mask_block", counted)
    q, k, v = _qkv(jax.random.PRNGKey(0), 1, 16 * 8, 2, 1, 8)
    t = jnp.ones((1, 16 * 8, 2, 8))
    f = _loss(lambda q, k, v: flash_attention(q, k, v, causal, None, 8, 8), t)
    with jax.disable_jit():
        o = flash_attention(q, k, v, causal, None, 8, 8)
        assert len(calls) == steps
        jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    assert len(calls) == 3 * steps
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(naive_attention(q, k, v, causal=causal)),
        atol=2e-5, rtol=1e-4)


def test_decode_matches_last_row_of_full_attention():
    """Decoding position S-1 against a full cache == last row of causal
    attention over the full sequence."""
    B, S, H, KV, hd = 2, 64, 4, 2, 16
    q, k, v = _qkv(jax.random.PRNGKey(1), B, S, H, KV, hd)
    full = naive_attention(q, k, v, causal=True)
    pos = jnp.arange(S)
    got = decode_attention(q[:, -1:], k, v, pos, jnp.int32(S - 1))
    np.testing.assert_allclose(np.asarray(full[:, -1:]), np.asarray(got),
                               atol=2e-5, rtol=1e-4)


def test_decode_window_masks_old_positions():
    B, S, H, KV, hd = 1, 64, 2, 2, 8
    q, k, v = _qkv(jax.random.PRNGKey(2), B, S, H, KV, hd)
    pos = jnp.arange(S)
    w = 16
    got = decode_attention(q[:, -1:], k, v, pos, jnp.int32(S - 1), window=w)
    want = naive_attention(q, k, v, causal=True, window=w)[:, -1:]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_ring_cache_decode_equivalence():
    """A rolled (ring) cache with position bookkeeping gives the same answer
    as the dense cache for sliding-window decode."""
    B, H, KV, hd, w = 1, 2, 2, 8, 16
    S = 48
    q, k, v = _qkv(jax.random.PRNGKey(3), B, S, H, KV, hd)
    # dense reference
    want = naive_attention(q, k, v, causal=True, window=w)[:, -1:]
    # ring cache of size w holding the last w positions
    slots = [(p % w) for p in range(S)]
    k_ring = jnp.zeros((B, w, KV, hd))
    v_ring = jnp.zeros((B, w, KV, hd))
    pos_ring = -jnp.ones((w,), jnp.int32)
    for p in range(S):
        k_ring = k_ring.at[:, slots[p]].set(k[:, p])
        v_ring = v_ring.at[:, slots[p]].set(v[:, p])
        pos_ring = pos_ring.at[slots[p]].set(p)
    got = decode_attention(q[:, -1:], k_ring, v_ring, pos_ring,
                           jnp.int32(S - 1), window=w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)
