"""Attention: naive reference, chunked flash (custom_vjp), and decode paths.

``flash_attention`` is a pure-JAX online-softmax implementation (a
lax.scan over query chunks, and in each a loop over only the key chunks
that the causal mask and the window leave visible, ``_block_plan``) with a
manual backward that recomputes per-block scores — O(S) memory at
32k/512k sequence lengths where a naive softmax would materialize S x S
scores.  Supports causal masking, GQA, static sliding windows, and value
heads narrower than the query/key heads (latent attention: 192-wide q and
k, 128-wide v); the scale is 1/sqrt of the query's head width.  The naive
path is the test oracle.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import registry as _registry

NEG_INF = -1e30


def _mask_block(q_pos, k_pos, causal: bool, window: Optional[int]):
    """(qc, kc) boolean mask: True = attend."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    return m


# ---------------------------------------------------------------------------
# reference implementation (oracle)
# ---------------------------------------------------------------------------
def naive_attention(q, k, v, *, causal=True, window=None, q_offset=0):
    """q: (B, Sq, H, hd); k: (B, Sk, KV, hd); v: (B, Sk, KV, hv).  fp32
    softmax."""
    B, Sq, H, hd = q.shape
    KV, hv = k.shape[2], v.shape[-1]
    G = H // KV
    qh = q.reshape(B, Sq, KV, G, hd).astype(jnp.float32) / math.sqrt(hd)
    s = jnp.einsum("bqngh,bcnh->bngqc", qh, k.astype(jnp.float32))
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(k.shape[1])
    mask = _mask_block(q_pos, k_pos, causal, window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bngqc,bcnh->bqngh", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hv).astype(q.dtype)


# ---------------------------------------------------------------------------
# flash attention: the block plan
# ---------------------------------------------------------------------------
def _block_plan(Sq, Sk, q_chunk, k_chunk, causal, window):
    """(nq, nk) static bool: the (q chunk, k chunk) blocks that hold at
    least one attended (q, k) pair.

    Over a block, d = k_pos - q_pos takes every integer from k0 - q1 to
    k1 - q0; causal needs some d <= 0 and a window some d > -window, and
    as the window's band lies in d <= 0 both hold at once exactly when
    each does.  Every other block would add exact zeros, so it is never
    computed.  Sets the ``attention.block_share`` gauge: visited blocks
    over nq x nk.
    """
    nq, nk = Sq // q_chunk, Sk // k_chunk
    q0 = np.arange(nq)[:, None] * q_chunk
    k0 = np.arange(nk)[None, :] * k_chunk
    visible = np.ones((nq, nk), bool)
    if causal:
        visible &= k0 <= q0 + q_chunk - 1
    if window is not None:
        visible &= k0 + k_chunk - 1 > q0 - window
    _registry().gauge(
        "attention.block_share",
        "flash attention blocks visited over nq x nk, last shape traced",
    ).set(visible.mean())
    return visible


def _row_ranges(visible):
    """Each row's visible columns as ``[lo, hi)``: the band a causal mask
    and a window leave is contiguous in every row (an empty row gets
    ``lo == hi``)."""
    any_ = visible.any(axis=1)
    lo = np.where(any_, visible.argmax(axis=1), 0)
    hi = np.where(any_, visible.shape[1] - visible[:, ::-1].argmax(axis=1), 0)
    return lo.astype(np.int32), hi.astype(np.int32)


def _row(x, i):
    return jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False)


def _add_row(x, i, dx):
    return jax.lax.dynamic_update_index_in_dim(x, _row(x, i) + dx, i, 0)


# ---------------------------------------------------------------------------
# flash attention: forward
# ---------------------------------------------------------------------------
def _flash_fwd(q, k, v, causal, window, q_chunk, k_chunk):
    B, Sq, H, hd = q.shape
    Sk, KV, hv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    nq, nk = Sq // q_chunk, Sk // k_chunk
    scale = 1.0 / math.sqrt(hd)

    qc = q.reshape(B, nq, q_chunk, KV, G, hd).swapaxes(0, 1)
    kc = k.reshape(B, nk, k_chunk, KV, hd).swapaxes(0, 1)
    vc = v.reshape(B, nk, k_chunk, KV, hv).swapaxes(0, 1)
    lo, hi = _row_ranges(_block_plan(Sq, Sk, q_chunk, k_chunk, causal,
                                     window))

    def q_step(_, xs):
        qb, q_idx, k_lo, k_hi = xs  # (B, qc, KV, G, hd)
        q_pos = q_idx * q_chunk + jnp.arange(q_chunk)
        qb32 = qb.astype(jnp.float32) * scale

        def k_step(k_idx, carry):
            m_run, l_run, acc = carry
            kb, vb = _row(kc, k_idx), _row(vc, k_idx)
            k_pos = k_idx * k_chunk + jnp.arange(k_chunk)
            s = jnp.einsum("bqngh,bcnh->bngqc", qb32, kb.astype(jnp.float32))
            mask = _mask_block(q_pos, k_pos, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l_run * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bngqc,bcnh->bngqh", p, vb.astype(jnp.float32))
            return m_new, l_new, acc * alpha[..., None] + pv

        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, hv), jnp.float32)
        # only the row's visible k chunks, ascending: traced bounds make
        # this a while loop of k_hi - k_lo steps
        m, l, acc = jax.lax.fori_loop(k_lo, k_hi, k_step, (m0, l0, a0))
        l_safe = jnp.maximum(l, 1e-30)
        o = acc / l_safe[..., None]
        lse = m + jnp.log(l_safe)
        return None, (o, lse)

    _, (o, lse) = jax.lax.scan(
        q_step, None, (qc, np.arange(nq, dtype=np.int32), lo, hi))
    # o: (nq, B, KV, G, qc, hv) -> (B, Sq, H, hv)
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, hv).astype(q.dtype)
    # lse: (nq, B, KV, G, qc) -> (B, KV, G, Sq)
    lse = lse.transpose(1, 2, 3, 0, 4).reshape(B, KV, G, Sq)
    return o, lse


# ---------------------------------------------------------------------------
# flash attention: backward (recompute scores per block)
# ---------------------------------------------------------------------------
def _flash_bwd_impl(q, k, v, o, lse, do, causal, window, q_chunk, k_chunk):
    B, Sq, H, hd = q.shape
    Sk, KV, hv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    nq, nk = Sq // q_chunk, Sk // k_chunk
    scale = 1.0 / math.sqrt(hd)

    qc = q.reshape(B, nq, q_chunk, KV, G, hd).swapaxes(0, 1)
    oc = o.reshape(B, nq, q_chunk, KV, G, hv).swapaxes(0, 1)
    doc = do.reshape(B, nq, q_chunk, KV, G, hv).swapaxes(0, 1)
    lsec = lse.reshape(B, KV, G, nq, q_chunk).transpose(3, 0, 1, 2, 4)
    kc = k.reshape(B, nk, k_chunk, KV, hd).swapaxes(0, 1)
    vc = v.reshape(B, nk, k_chunk, KV, hv).swapaxes(0, 1)

    # delta = rowsum(do * o): (nq, B, KV, G, qc)
    delta = jnp.einsum("nbqkgh,nbqkgh->nbkgq",
                       doc.astype(jnp.float32), oc.astype(jnp.float32))
    lo, hi = _row_ranges(_block_plan(Sq, Sk, q_chunk, k_chunk, causal,
                                     window))

    def q_step(carry, xs):
        qb, dob, lseb, deltab, q_idx, k_lo, k_hi = xs
        q_pos = q_idx * q_chunk + jnp.arange(q_chunk)
        qb32 = qb.astype(jnp.float32) * scale
        dob32 = dob.astype(jnp.float32)

        def k_step(k_idx, carry2):
            dq_acc, dk_all, dv_all = carry2
            kb, vb = _row(kc, k_idx), _row(vc, k_idx)
            k_pos = k_idx * k_chunk + jnp.arange(k_chunk)
            s = jnp.einsum("bqngh,bcnh->bngqc", qb32, kb.astype(jnp.float32))
            mask = _mask_block(q_pos, k_pos, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jnp.exp(s - lseb[..., None])  # (B, KV, G, qc, kc)
            dp = jnp.einsum("bqngh,bcnh->bngqc", dob32, vb.astype(jnp.float32))
            ds = p * (dp - deltab[..., None])  # fp32
            dq_acc = dq_acc + jnp.einsum("bngqc,bcnh->bqngh", ds,
                                         kb.astype(jnp.float32)) * scale
            dk_b = jnp.einsum("bngqc,bqngh->bcnh", ds, qb32)
            dv_b = jnp.einsum("bngqc,bqngh->bcnh", p, dob32)
            return (dq_acc, _add_row(dk_all, k_idx, dk_b),
                    _add_row(dv_all, k_idx, dv_b))

        dq0 = jnp.zeros((B, q_chunk, KV, G, hd), jnp.float32)
        dq, dk_all, dv_all = jax.lax.fori_loop(k_lo, k_hi, k_step,
                                               (dq0, *carry))
        return (dk_all, dv_all), dq

    dk0 = jnp.zeros((nk, B, k_chunk, KV, hd), jnp.float32)
    dv0 = jnp.zeros((nk, B, k_chunk, KV, hv), jnp.float32)
    (dk, dv), dq = jax.lax.scan(
        q_step, (dk0, dv0),
        (qc, doc, lsec, delta, np.arange(nq, dtype=np.int32), lo, hi))
    dq = dq.swapaxes(0, 1).reshape(B, Sq, H, hd).astype(q.dtype)
    dk = dk.swapaxes(0, 1).reshape(B, Sk, KV, hd).astype(k.dtype)
    dv = dv.swapaxes(0, 1).reshape(B, Sk, KV, hv).astype(v.dtype)
    # note: dk_b above used scaled q; ds already has the 1/sqrt(hd) folded via
    # qb32, so dk is correct as-is.
    return dq, dk, dv


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=True, window=None, q_chunk=512,
                    k_chunk=512):
    o, _ = _flash_fwd(q, k, v, causal, window, q_chunk, k_chunk)
    return o


def _flash_vjp_fwd(q, k, v, causal, window, q_chunk, k_chunk):
    o, lse = _flash_fwd(q, k, v, causal, window, q_chunk, k_chunk)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, window, q_chunk, k_chunk, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, o, lse, do, causal, window,
                                 q_chunk, k_chunk)
    return dq, dk, dv


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def attention_any(q, k, v, *, causal=True, window=None, q_chunk=512,
                  k_chunk=512):
    """Dispatch: chunked flash when divisible and long enough, else naive."""
    Sq, Sk = q.shape[1], k.shape[1]
    if Sq % q_chunk == 0 and Sk % k_chunk == 0 and Sq > q_chunk:
        return flash_attention(q, k, v, causal, window, q_chunk, k_chunk)
    return naive_attention(q, k, v, causal=causal, window=window)


# ---------------------------------------------------------------------------
# decode: one query against a (possibly ring) KV cache
# ---------------------------------------------------------------------------
def decode_attention(q, k_cache, v_cache, cache_pos, cur_pos, *, window=None):
    """q: (B, 1, H, hd); caches: (B, Smax, KV, hd);
    cache_pos: (Smax,) or (B, Smax) absolute position of each slot (-1 empty);
    cur_pos: scalar current absolute position.
    """
    B, _, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    qh = q.reshape(B, KV, G, hd).astype(jnp.float32) / math.sqrt(hd)
    s = jnp.einsum("bngh,bcnh->bngc", qh, k_cache.astype(jnp.float32))
    pos = cache_pos if cache_pos.ndim == 2 else cache_pos[None, :]
    valid = (pos >= 0) & (pos <= cur_pos)
    if window is not None:
        valid &= pos > (cur_pos - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bngc,bcnh->bngh", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def paged_decode_attention(q, k_flat, v_flat, tables, positions, page_size):
    """Single-query attention over a paged KV pool (unfused reference path).

    q: (S, 1, H, hd) — one query per *slot*; k_flat, v_flat:
    (n_pages * page_size, KV, hd) — the shared block pool, flattened, with
    this step's k/v already written; tables: (S, maxp) int32 per-slot page
    table; positions: (S,) absolute position per slot.

    Each slot's pages are gathered in **logical** order (so the result is
    invariant to the physical page permutation) and attended with exactly
    the ops :func:`decode_attention` uses — fp32 softmax, same einsum
    orders — which keeps the paged path bitwise-equal to the contiguous
    ring on a single-sequence stream (validity is ``logical index <=
    position``; full attention only — sliding windows keep the ring path).
    """
    S, _, H, hd = q.shape
    KV = k_flat.shape[1]
    G = H // KV
    maxp = tables.shape[1]
    qh = q.reshape(S, KV, G, hd).astype(jnp.float32) / math.sqrt(hd)
    gidx = ((tables * page_size)[:, :, None]
            + jnp.arange(page_size)[None, None]).reshape(S, maxp * page_size)
    kg = k_flat[gidx]                                 # (S, maxp*ps, KV, hd)
    vg = v_flat[gidx]
    s = jnp.einsum("bngh,bcnh->bngc", qh, kg.astype(jnp.float32))
    valid = jnp.arange(maxp * page_size)[None, :] <= positions[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bngc,bcnh->bngh", p, vg.astype(jnp.float32))
    return o.reshape(S, 1, H, hd).astype(q.dtype)
