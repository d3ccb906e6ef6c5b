"""Attention: naive reference, chunked flash (custom_vjp), and decode paths.

``flash_attention`` is a pure-JAX online-softmax implementation (lax.scan
over query/key chunks) with a manual backward that recomputes per-block
scores — O(S) memory at 32k/512k sequence lengths where a naive softmax
would materialize S x S scores.  Supports causal masking, GQA, static
sliding windows, and value heads narrower than the query/key heads (latent
attention: 192-wide q and k, 128-wide v); the scale is 1/sqrt of the
query's head width.  The naive path is the test oracle.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

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
# flash attention: forward
# ---------------------------------------------------------------------------
def _n_win(window, k_chunk, nk):
    """number of k chunks a q chunk can see under a sliding window."""
    return min(nk, -(-window // k_chunk) + 1)


def _flash_fwd(q, k, v, causal, window, q_chunk, k_chunk, window_slice=False):
    B, Sq, H, hd = q.shape
    Sk, KV, hv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    nq, nk = Sq // q_chunk, Sk // k_chunk
    scale = 1.0 / math.sqrt(hd)

    qc = q.reshape(B, nq, q_chunk, KV, G, hd)
    kc = k.reshape(B, nk, k_chunk, KV, hd)
    vc = v.reshape(B, nk, k_chunk, KV, hv)
    sliced = window_slice and window is not None and causal and nq == nk

    def q_step(_, qi):
        qb, q_idx = qi  # (B, qc, KV, G, hd)
        q_pos = q_idx * q_chunk + jnp.arange(q_chunk)
        qb32 = qb.astype(jnp.float32) * scale

        def block(carry, kb, vb, k_idx, valid=True):
            m_run, l_run, acc = carry
            k_pos = k_idx * k_chunk + jnp.arange(k_chunk)
            s = jnp.einsum("bqngh,bcnh->bngqc", qb32, kb.astype(jnp.float32))
            mask = (q_pos[:, None] >= k_pos[None, :]) if causal else jnp.ones(
                (q_chunk, k_chunk), bool)
            if window is not None:
                mask &= k_pos[None, :] > (q_pos[:, None] - window)
            mask &= valid  # sliced iters clipped to chunk 0 must not re-count
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l_run * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bngqc,bcnh->bngqh", p, vb.astype(jnp.float32))
            acc = acc * alpha[..., None] + pv
            return (m_new, l_new, acc)

        def k_step(carry, ki):
            kb, vb, k_idx = ki
            return block(carry, kb, vb, k_idx), None

        def k_step_sliced(carry, t):
            # only the in-window chunks: k_idx in [q_idx - n_win + 1, q_idx];
            # clipped duplicates are invalidated via the mask
            raw = q_idx - (nwin - 1) + t
            k_idx = jnp.clip(raw, 0, nk - 1)
            kb = jax.lax.dynamic_index_in_dim(kc, k_idx, 1, keepdims=False)
            vb = jax.lax.dynamic_index_in_dim(vc, k_idx, 1, keepdims=False)
            return block(carry, kb, vb, k_idx, valid=(raw >= 0)), None

        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, hv), jnp.float32)
        if sliced:
            nwin = _n_win(window, k_chunk, nk)
            (m, l, acc), _ = jax.lax.scan(k_step_sliced, (m0, l0, a0),
                                          jnp.arange(nwin))
        else:
            (m, l, acc), _ = jax.lax.scan(
                k_step, (m0, l0, a0), (kc.swapaxes(0, 1), vc.swapaxes(0, 1),
                                       jnp.arange(nk)))
        l_safe = jnp.maximum(l, 1e-30)
        o = acc / l_safe[..., None]
        lse = m + jnp.log(l_safe)
        return None, (o, lse)

    _, (o, lse) = jax.lax.scan(q_step, None, (qc.swapaxes(0, 1), jnp.arange(nq)))
    # o: (nq, B, KV, G, qc, hd) -> (B, Sq, H, hd)
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, hv).astype(q.dtype)
    # lse: (nq, B, KV, G, qc) -> (B, KV, G, Sq)
    lse = lse.transpose(1, 2, 3, 0, 4).reshape(B, KV, G, Sq)
    return o, lse


# ---------------------------------------------------------------------------
# flash attention: backward (recompute scores per block)
# ---------------------------------------------------------------------------
def _flash_bwd_impl(q, k, v, o, lse, do, causal, window, q_chunk, k_chunk,
                    window_slice=False):
    B, Sq, H, hd = q.shape
    Sk, KV, hv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    nq, nk = Sq // q_chunk, Sk // k_chunk
    scale = 1.0 / math.sqrt(hd)

    qc = q.reshape(B, nq, q_chunk, KV, G, hd).swapaxes(0, 1)
    oc = o.reshape(B, nq, q_chunk, KV, G, hv).swapaxes(0, 1)
    doc = do.reshape(B, nq, q_chunk, KV, G, hv).swapaxes(0, 1)
    lsec = lse.reshape(B, KV, G, nq, q_chunk).transpose(3, 0, 1, 2, 4)
    kc = k.reshape(B, nk, k_chunk, KV, hd)
    vc = v.reshape(B, nk, k_chunk, KV, hv)

    # delta = rowsum(do * o): (nq, B, KV, G, qc)
    delta = jnp.einsum("nbqkgh,nbqkgh->nbkgq",
                       doc.astype(jnp.float32), oc.astype(jnp.float32))
    sliced = window_slice and window is not None and causal and nq == nk
    nwin = _n_win(window, k_chunk, nk) if sliced else nk

    def q_step(carry, qi):
        dk_all, dv_all = carry
        qb, dob, lseb, deltab, q_idx = qi
        q_pos = q_idx * q_chunk + jnp.arange(q_chunk)
        qb32 = qb.astype(jnp.float32) * scale
        dob32 = dob.astype(jnp.float32)

        def k_step(carry2, ki):
            dq_acc, dk_all, dv_all = carry2
            if sliced:
                raw = q_idx - (nwin - 1) + ki
                k_idx = jnp.clip(raw, 0, nk - 1)
                valid = raw >= 0
            else:
                k_idx = ki
                valid = True
            kb = jax.lax.dynamic_index_in_dim(kc, k_idx, axis=1, keepdims=False)
            vb = jax.lax.dynamic_index_in_dim(vc, k_idx, axis=1, keepdims=False)
            k_pos = k_idx * k_chunk + jnp.arange(k_chunk)
            s = jnp.einsum("bqngh,bcnh->bngqc", qb32, kb.astype(jnp.float32))
            mask = (q_pos[:, None] >= k_pos[None, :]) if causal else jnp.ones(
                (q_chunk, k_chunk), bool)
            if window is not None:
                mask &= k_pos[None, :] > (q_pos[:, None] - window)
            mask &= valid
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jnp.exp(s - lseb[..., None])  # (B, KV, G, qc, kc)
            dp = jnp.einsum("bqngh,bcnh->bngqc", dob32, vb.astype(jnp.float32))
            ds = p * (dp - deltab[..., None])  # fp32
            dq_acc = dq_acc + jnp.einsum("bngqc,bcnh->bqngh", ds,
                                         kb.astype(jnp.float32)) * scale
            dk_b = jnp.einsum("bngqc,bqngh->bcnh", ds, qb32)
            dv_b = jnp.einsum("bngqc,bqngh->bcnh", p, dob32)
            dk_all = jax.lax.dynamic_update_index_in_dim(
                dk_all, jax.lax.dynamic_index_in_dim(dk_all, k_idx, 1, False) + dk_b,
                k_idx, 1)
            dv_all = jax.lax.dynamic_update_index_in_dim(
                dv_all, jax.lax.dynamic_index_in_dim(dv_all, k_idx, 1, False) + dv_b,
                k_idx, 1)
            return (dq_acc, dk_all, dv_all), None

        dq0 = jnp.zeros((B, q_chunk, KV, G, hd), jnp.float32)
        (dq, dk_all, dv_all), _ = jax.lax.scan(
            k_step, (dq0, dk_all, dv_all), jnp.arange(nwin if sliced else nk))
        return (dk_all, dv_all), dq

    dk0 = jnp.zeros((B, nk, k_chunk, KV, hd), jnp.float32)
    dv0 = jnp.zeros((B, nk, k_chunk, KV, hv), jnp.float32)
    (dk, dv), dq = jax.lax.scan(
        q_step, (dk0, dv0), (qc, doc, lsec, delta, jnp.arange(nq)))
    dq = dq.swapaxes(0, 1).reshape(B, Sq, H, hd).astype(q.dtype)
    dk = dk.reshape(B, Sk, KV, hd).astype(k.dtype)
    dv = dv.reshape(B, Sk, KV, hv).astype(v.dtype)
    # note: dk_b above used scaled q; ds already has the 1/sqrt(hd) folded via
    # qb32, so dk is correct as-is.
    return dq, dk, dv


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, window=None, q_chunk=512,
                    k_chunk=512, window_slice=False):
    o, _ = _flash_fwd(q, k, v, causal, window, q_chunk, k_chunk, window_slice)
    return o


def _flash_vjp_fwd(q, k, v, causal, window, q_chunk, k_chunk, window_slice):
    o, lse = _flash_fwd(q, k, v, causal, window, q_chunk, k_chunk, window_slice)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, window, q_chunk, k_chunk, window_slice, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, o, lse, do, causal, window,
                                 q_chunk, k_chunk, window_slice)
    return dq, dk, dv


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def attention_any(q, k, v, *, causal=True, window=None, q_chunk=512,
                  k_chunk=512, window_slice=False):
    """Dispatch: chunked flash when divisible and long enough, else naive."""
    Sq, Sk = q.shape[1], k.shape[1]
    if Sq % q_chunk == 0 and Sk % k_chunk == 0 and Sq > q_chunk:
        return flash_attention(q, k, v, causal, window, q_chunk, k_chunk,
                               window_slice)
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
