"""Pure-jnp oracles for the Pallas kernels.

``decode_step_ref`` and ``paged_decode_step_ref`` walk the kernels' KV
blocks with the kernels' own online-softmax helpers, so they match them bit
for bit and pin the blocking and the DMA plumbing.  ``*_dense_ref`` attend
with one full softmax over the whole cache and share no code with the
kernels, so they pin the attention math itself (to fp32 rounding).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.decode_step import (
    BLOCK_KV,
    attend_block,
    finish,
    init_carry,
    kv_block,
)
from repro.kernels.rng import normal_from_counter


def langevin_update_ref(x: jnp.ndarray, g: jnp.ndarray, seed: jnp.ndarray,
                        gamma, scale) -> jnp.ndarray:
    """x, g: (R, L); seed (2,) uint32 — same counter scheme as the kernel
    (row-major global element index), fp32 arithmetic, one rounding to
    x's dtype."""
    R, L = x.shape
    counter = jnp.arange(R * L, dtype=jnp.uint32).reshape(R, L)
    xi = normal_from_counter(seed[0], seed[1], counter)
    out = (x.astype(jnp.float32) - jnp.float32(gamma) * g.astype(jnp.float32)
           + jnp.float32(scale) * xi)
    return out.astype(x.dtype)


def delay_gather_ref(history: jnp.ndarray, slots: jnp.ndarray) -> jnp.ndarray:
    """history: (depth, N); slots: (N,) -> (N,)."""
    return jnp.take_along_axis(history, slots[None, :], axis=0)[0]


def decode_step_ref(q, k_new, v_new, k_cache, v_cache, valid, slot):
    """Oracle for the fused decode step — the same slot select, KV blocks,
    online softmax and op order as the kernel body, one row at a time.

    q: (B, KV, G, hd); k_new/v_new: (B, KV, hd); caches: (B, smax, KV, hd);
    valid: (smax,) int32; slot: scalar int32.
    """
    B, KV, G, hd = q.shape
    smax = k_cache.shape[1]
    bs = kv_block(smax)
    sel = jax.lax.broadcasted_iota(jnp.int32, k_cache.shape[1:], 0) == slot
    k = jnp.where(sel[None], k_new[:, None].astype(k_cache.dtype), k_cache)
    v = jnp.where(sel[None], v_new[:, None].astype(v_cache.dtype), v_cache)
    rows = jnp.repeat(jnp.asarray(valid, jnp.int32), KV)[None] != 0
    outs = []
    for b in range(B):
        q32 = q[b].reshape(KV * G, hd).astype(jnp.float32) * (
            1.0 / math.sqrt(hd))
        carry = init_carry(KV * G, hd)
        for j in range(smax // bs):
            blk = slice(j * bs, (j + 1) * bs)
            carry = attend_block(q32, k[b, blk], v[b, blk],
                                 rows[:, j * bs * KV:(j + 1) * bs * KV], carry)
        outs.append(finish(carry, q.dtype))
    return jnp.stack(outs).reshape(q.shape), k, v


def _dense_attend(q, k, v, keep):
    """q: (B, KV, G, hd); k, v: (B, T, KV, hd); keep: (B, T) bool.  One
    fp32 softmax over all T rows per head, normalized before the value
    sum."""
    q32 = q.astype(jnp.float32) / math.sqrt(q.shape[-1])
    s = jnp.einsum("bngh,bcnh->bngc", q32, k.astype(jnp.float32))
    s = jnp.where(keep[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bngc,bcnh->bngh", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def decode_step_dense_ref(q, k_new, v_new, k_cache, v_cache, valid, slot):
    """:func:`decode_step_ref`'s contract, attended with one dense softmax
    (independent of the kernel's code)."""
    k = k_cache.at[:, slot].set(k_new.astype(k_cache.dtype))
    v = v_cache.at[:, slot].set(v_new.astype(v_cache.dtype))
    keep = jnp.broadcast_to(jnp.asarray(valid) != 0, k.shape[:2])
    return _dense_attend(q, k, v, keep), k, v


def paged_decode_step_dense_ref(q, k_new, v_new, k_pages, v_pages, tables,
                                pos):
    """:func:`paged_decode_step_ref`'s contract: write each slot's new row
    into its page, gather the slot's pages in logical order and attend to
    logical rows ``<= pos`` with one dense softmax (independent of the
    kernel's code)."""
    S, KV, G, hd = q.shape
    ps = k_pages.shape[1]
    maxp = tables.shape[1]
    kf = k_pages.reshape(-1, KV, hd)
    vf = v_pages.reshape(-1, KV, hd)
    widx = tables[jnp.arange(S), pos // ps] * ps + pos % ps
    kf = kf.at[widx].set(k_new.astype(kf.dtype))
    vf = vf.at[widx].set(v_new.astype(vf.dtype))
    gidx = ((tables * ps)[:, :, None]
            + jnp.arange(ps)[None, None]).reshape(S, maxp * ps)
    keep = jnp.arange(maxp * ps)[None] <= pos[:, None]
    return (_dense_attend(q, kf[gidx], vf[gidx], keep),
            kf.reshape(k_pages.shape), vf.reshape(v_pages.shape))


def paged_decode_step_ref(q, k_new, v_new, k_pages, v_pages, tables, pos):
    """Oracle for the fused *paged* decode step — the same logical-order
    page blocks (clamped table reads), new-row overlay, online softmax and
    op order as the kernel body, one slot at a time.  It walks every block
    of the table; blocks past a slot's position are fully masked, which
    leaves the softmax state bit-for-bit unchanged.

    q: (S, KV, G, hd); k_new/v_new: (S, KV, hd); k_pages/v_pages:
    (n_pages, page_size, KV, hd) shared pool; tables: (S, maxp) int32;
    pos: (S,) int32.  Returns (o, k_pages', v_pages').
    """
    S, KV, G, hd = q.shape
    ps = k_pages.shape[1]
    maxp = tables.shape[1]
    ppb = max(1, min(maxp, BLOCK_KV // ps))
    width = ppb * ps
    k_new = k_new.astype(k_pages.dtype)
    v_new = v_new.astype(v_pages.dtype)
    t = jnp.arange(width)
    lane = jnp.arange(width * KV)[None] // KV
    outs = []
    for s in range(S):
        q32 = q[s].reshape(KV * G, hd).astype(jnp.float32) * (
            1.0 / math.sqrt(hd))
        carry = init_carry(KV * G, hd)
        for blk in range(-(-maxp // ppb)):
            idx = jnp.minimum(blk * ppb + jnp.arange(ppb), maxp - 1)
            pages = tables[s, idx]
            base = blk * width
            new = (t + base == pos[s])[:, None, None]
            k = jnp.where(new, k_new[s][None],
                          k_pages[pages].reshape(width, KV, hd))
            v = jnp.where(new, v_new[s][None],
                          v_pages[pages].reshape(width, KV, hd))
            carry = attend_block(q32, k, v, lane + base <= pos[s], carry)
        outs.append(finish(carry, q.dtype))
    kf = k_pages.reshape(-1, KV, hd)
    vf = v_pages.reshape(-1, KV, hd)
    widx = tables[jnp.arange(S), pos // ps] * ps + pos % ps
    return (jnp.stack(outs).reshape(q.shape),
            kf.at[widx].set(k_new).reshape(k_pages.shape),
            vf.at[widx].set(v_new).reshape(v_pages.shape))


def grouped_matmul_ref(lhs, rhs, group_sizes):
    """Oracle of ``kernels.grouped_matmul``: the same product through
    ``jax.lax.ragged_dot`` (XLA's own grouped matmul), the rows past the
    last group multiplied by a zero matrix."""
    m = lhs.shape[0]
    sizes = jnp.concatenate([group_sizes.astype(jnp.int32),
                             (m - jnp.sum(group_sizes)).astype(jnp.int32)[None]])
    rhs = jnp.concatenate([rhs.astype(lhs.dtype),
                           jnp.zeros((1,) + rhs.shape[1:], lhs.dtype)])
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)
