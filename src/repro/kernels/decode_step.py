"""Pallas TPU kernels: fused streaming decode steps (the per-token hot path).

One decode step of the chain-bank BMA server, per (chain, batch) row: the
new token's k/v is written into the cache at the current position, then
single-query attention runs over the cache.  Unfused, XLA emits (write k),
(write v), (read k cache), (read v cache).  These kernels fuse the write
with the attention read: the cache streams through VMEM once per operand,
and only the new row is written back to HBM.

- :func:`decode_step_2d` serves the contiguous ring cache
  ``(B, smax, KV, hd)``.  The grid walks ``(row, KV block)``: each step
  holds one ``(bs, KV, hd)`` block per operand (bs <= 512, 1 MiB in bf16 at
  KV 8, hd 128), so VMEM use does not grow with smax.
- :func:`paged_decode_step` serves a paged pool ``(n_pages, page_size, KV,
  hd)`` shared by every slot.  The page table and positions arrive by
  scalar prefetch; the kernel DMAs the slot's pages, a block of pages at a
  time, and stops at the block holding the slot's position.

Both attend with an online softmax over KV blocks (running max, running
sum, fp32 accumulator), which ``ref.py`` mirrors block for block.  Scores
for all query heads against one block are one ``(H, hd) x (bs*KV, hd)^T``
matmul; a head mask keeps query head ``n*G + g`` on its own kv head ``n``.
The new row is overlaid on the block in VMEM, so the result does not depend
on whether the read of that block saw the row's write.  Softmax and
accumulation are fp32 and the output is rounded to q's dtype once.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import on_backend

NEG_INF = -1e30
BLOCK_KV = 512  # cache rows per online-softmax block

__all__ = ["decode_step_2d", "paged_decode_step", "kv_block", "attend_block",
           "init_carry", "finish"]


def kv_block(n: int) -> int:
    """Rows per KV block for ``n`` cache rows: ``n`` itself when it is at
    most :data:`BLOCK_KV`, else the largest power-of-two divisor of ``n``
    up to :data:`BLOCK_KV` (a block never overhangs the cache)."""
    if n <= BLOCK_KV:
        return n
    b = BLOCK_KV
    while n % b:
        b //= 2
    return b


def init_carry(H: int, hd: int):
    """Online-softmax state (running max, running sum, accumulator)."""
    return (jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, hd), jnp.float32))


def attend_block(q32, k, v, keep_rows, carry):
    """One KV block of single-query attention, all heads at once.

    q32: (H, hd) fp32, already scaled; k, v: (T, KV, hd); keep_rows:
    (1, T*KV) bool, whether each cache row may be attended (repeated over
    the KV heads of the row); carry: :func:`init_carry` state.
    """
    T, KV, hd = k.shape
    H = q32.shape[0]
    m, l, acc = carry
    k2 = k.astype(jnp.float32).reshape(T * KV, hd)
    v2 = v.astype(jnp.float32).reshape(T * KV, hd)
    r = jax.lax.broadcasted_iota(jnp.int32, (H, T * KV), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (H, T * KV), 1)
    keep = (r // (H // KV) == c % KV) & keep_rows
    s = jax.lax.dot_general(q32, k2, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.where(keep, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m - m_new)
    l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
    acc = alpha * acc + jnp.dot(p, v2, preferred_element_type=jnp.float32)
    return m_new, l, acc


def finish(carry, dtype):
    """Normalized attention output (H, hd) in ``dtype``."""
    _, l, acc = carry
    return (acc / l).astype(dtype)


def _overlay(block, row, at):
    """``block`` (T, KV, hd) with row ``at`` (block-relative; may be out of
    range) replaced by ``row`` (KV, hd)."""
    t = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return jnp.where(t == at, row[None], block)


def _chain_batched(chains, n_shared: int = 0):
    """Turn ``chains(*args)`` — a kernel whose operands carry a leading
    chain axis, except the first ``n_shared`` ones — into a single-chain
    function whose ``vmap`` over chains calls ``chains`` once.

    The pallas batching rule would add the chain axis to the grid, but a
    TPU kernel cannot take a batched block of an HBM (``pl.ANY``) operand.
    Under ``vmap`` unbatched operands are broadcast along the chain axis;
    the shared operands (page tables, positions) must stay unbatched.
    """

    @jax.custom_batching.custom_vmap
    def one(*args):
        out = chains(*args[:n_shared], *(a[None] for a in args[n_shared:]))
        return tuple(o[0] for o in out)

    @one.def_vmap
    def _rule(axis_size, in_batched, *args):
        if any(in_batched[:n_shared]):
            raise ValueError(
                f"the first {n_shared} operands are shared by every chain and "
                "cannot carry the vmapped axis")
        rest = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args[n_shared:], in_batched[n_shared:])]
        out = chains(*args[:n_shared], *rest)
        return tuple(out), (True,) * len(out)

    return one


# ---------------------------------------------------------------------------
# contiguous ring cache
# ---------------------------------------------------------------------------
def _kernel(slot_ref, q_ref, kn_ref, vn_ref, kc_ref, vc_ref, valid_ref,
            ko_hbm, vo_hbm, o_ref, m_sc, l_sc, acc_sc, sem):
    c, b, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bs = kc_ref.shape[2]
    slot = slot_ref[c]
    writes = [pltpu.make_async_copy(
        src, dst.at[pl.ds(c, 1), pl.ds(b, 1), pl.ds(slot, 1)], sem.at[i])
        for i, (src, dst) in enumerate(((kn_ref, ko_hbm), (vn_ref, vo_hbm)))]

    @pl.when(j == 0)
    def _():
        # the only HBM write of the step: the new row into its slot
        for w in writes:
            w.start()
        m0, l0, a0 = init_carry(*acc_sc.shape)
        m_sc[...], l_sc[...], acc_sc[...] = m0, l0, a0

    hd = q_ref.shape[-1]
    q32 = q_ref[0, 0].astype(jnp.float32) * (1.0 / math.sqrt(hd))
    k = _overlay(kc_ref[0, 0], kn_ref[0, 0, 0], slot - j * bs)
    v = _overlay(vc_ref[0, 0], vn_ref[0, 0, 0], slot - j * bs)
    carry = attend_block(q32, k, v, valid_ref[0] != 0,
                         (m_sc[...], l_sc[...], acc_sc[...]))
    m_sc[...], l_sc[...], acc_sc[...] = carry

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[0, 0] = finish(carry, o_ref.dtype)
        for w in writes:
            w.wait()


def _call(q, k_new, v_new, k_cache, v_cache, valid, slot, *,
          interpret: bool):
    C, B, H, hd = q.shape
    smax, KV = k_cache.shape[2:4]
    bs = kv_block(smax)
    row = lambda c, b, j, *_: (c, b, 0, 0, 0)  # noqa: E731
    blk = lambda c, b, j, *_: (c, b, j, 0, 0)  # noqa: E731
    head = pl.BlockSpec((1, 1, H, hd), lambda c, b, j, *_: (c, b, 0, 0))
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(C, B, smax // bs),
            in_specs=[
                head,
                pl.BlockSpec((1, 1, 1, KV, hd), row),
                pl.BlockSpec((1, 1, 1, KV, hd), row),
                pl.BlockSpec((1, 1, bs, KV, hd), blk),
                pl.BlockSpec((1, 1, bs, KV, hd), blk),
                pl.BlockSpec((1, 1, bs * KV), lambda c, b, j, *_: (c, 0, j)),
            ],
            out_specs=[any_, any_, head],
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, hd), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
                   jax.ShapeDtypeStruct(q.shape, q.dtype)],
        input_output_aliases={4: 0, 5: 1},  # caches update in place
        interpret=interpret,
        name="decode_step",
    )(slot, q, k_new[:, :, None], v_new[:, :, None], k_cache, v_cache, valid)


_decode_chains = _chain_batched(lambda *a: on_backend(_call, *a))


@jax.jit
def decode_step_2d(q, k_new, v_new, k_cache, v_cache, valid, slot):
    """q: (B, KV, G, hd); k_new, v_new: (B, KV, hd);
    k_cache, v_cache: (B, smax, KV, hd); valid: (smax,) int32 (1 = attend);
    slot: (1,) int32 — the ring slot the new k/v lands in.

    Returns (o (B, KV, G, hd) in q.dtype, k_cache', v_cache') with the slot
    row replaced in both caches (aliased in place).  A chain-vmapped caller
    gets one kernel over a ``(chain, row, KV block)`` grid.
    """
    B, KV, G, hd = q.shape
    # (1, smax*KV): a unit row dim keeps the block legal under the chain axis
    rows = jnp.repeat(jnp.asarray(valid, jnp.int32), KV)[None]
    kc, vc, o = _decode_chains(
        q.reshape(B, KV * G, hd), k_new.astype(k_cache.dtype),
        v_new.astype(v_cache.dtype), k_cache, v_cache, rows,
        jnp.asarray(slot, jnp.int32).reshape(()))
    return o.reshape(B, KV, G, hd), kc, vc


# ---------------------------------------------------------------------------
# paged pool: page-table DMA gather
# ---------------------------------------------------------------------------
def _paged_kernel(tbl_ref, pos_ref, q_ref, kn_ref, vn_ref, kp_hbm, vp_hbm,
                  ko_hbm, vo_hbm, o_ref, kbuf, vbuf, sem, wsem, *,
                  pages_per_block: int):
    # ko_hbm / vo_hbm alias kp_hbm / vp_hbm: pages are read through the
    # inputs and the new rows written through the outputs
    c, s = pl.program_id(0), pl.program_id(1)
    maxp = tbl_ref.shape[1]
    ps, KV, hd = kbuf.shape[1:]
    width = pages_per_block * ps
    pos = pos_ref[s]
    # store the new k/v into this slot's page for the current position; only
    # these two rows of the shared pool are touched, every other page
    # survives bit-for-bit
    page, off = tbl_ref[s, pos // ps], pos % ps
    writes = [pltpu.make_async_copy(
        src, dst.at[pl.ds(c, 1), pl.ds(page, 1), pl.ds(off, 1)], wsem.at[i])
        for i, (src, dst) in enumerate(((kn_ref, ko_hbm), (vn_ref, vo_hbm)))]
    for w in writes:
        w.start()

    q32 = q_ref[0, 0].astype(jnp.float32) * (1.0 / math.sqrt(hd))

    def block(blk, carry):
        # gather this slot's pages in *logical* order (the attention result
        # is invariant to how the allocator permuted the physical pages);
        # entries past the slot's pages name real pages (the garbage page),
        # so the buffer never holds stale data
        copies = []
        for i in range(pages_per_block):
            pg = tbl_ref[s, jnp.minimum(blk * pages_per_block + i, maxp - 1)]
            copies += [
                pltpu.make_async_copy(kp_hbm.at[c, pg], kbuf.at[i], sem),
                pltpu.make_async_copy(vp_hbm.at[c, pg], vbuf.at[i], sem)]
        for cp in copies:
            cp.start()
        for cp in copies:
            cp.wait()
        base = blk * width
        k = _overlay(kbuf[...].reshape(width, KV, hd), kn_ref[0, 0, 0],
                     pos - base)
        v = _overlay(vbuf[...].reshape(width, KV, hd), vn_ref[0, 0, 0],
                     pos - base)
        t = jax.lax.broadcasted_iota(jnp.int32, (1, width * KV), 1) // KV
        return attend_block(q32, k, v, t + base <= pos, carry)

    carry = jax.lax.fori_loop(0, pos // width + 1, block,
                              init_carry(q_ref.shape[2], hd))
    o_ref[0, 0] = finish(carry, o_ref.dtype)
    for w in writes:
        w.wait()


def _paged_call(tables, pos, q, k_new, v_new, k_pages, v_pages, *,
                interpret: bool):
    C, S, H, hd = q.shape
    ps, KV = k_pages.shape[2:4]
    maxp = tables.shape[1]
    ppb = max(1, min(maxp, BLOCK_KV // ps))
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    row = lambda c, s, *_: (c, s, 0, 0, 0)  # noqa: E731
    head = pl.BlockSpec((1, 1, H, hd), lambda c, s, *_: (c, s, 0, 0))
    return pl.pallas_call(
        partial(_paged_kernel, pages_per_block=ppb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(C, S),
            in_specs=[head,
                      pl.BlockSpec((1, 1, 1, KV, hd), row),
                      pl.BlockSpec((1, 1, 1, KV, hd), row),
                      any_, any_],
            out_specs=[any_, any_, head],
            scratch_shapes=[pltpu.VMEM((ppb, ps, KV, hd), k_pages.dtype),
                            pltpu.VMEM((ppb, ps, KV, hd), v_pages.dtype),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
                   jax.ShapeDtypeStruct(q.shape, q.dtype)],
        input_output_aliases={5: 0, 6: 1},  # pools update in place
        interpret=interpret,
        name="paged_decode_step",
    )(tables, pos, q, k_new[:, :, None], v_new[:, :, None], k_pages, v_pages)


_paged_chains = _chain_batched(lambda *a: on_backend(_paged_call, *a),
                               n_shared=2)


@jax.jit
def paged_decode_step(q, k_new, v_new, k_pages, v_pages, tables, pos):
    """Fused paged decode step: slot-table gather + slot write + attention.

    q: (S, KV, G, hd); k_new, v_new: (S, KV, hd); k_pages, v_pages:
    (n_pages, page_size, KV, hd) — the block pool **shared by every slot**;
    tables: (S, maxp) int32 per-slot page table (logical page j of slot i
    lives in physical page ``tables[i, j]``, every entry a valid page);
    pos: (S,) int32 absolute position the new token is written at (and the
    highest logical index attended — validity is ``logical index <= pos``,
    full attention only).

    Returns (o (S, KV, G, hd) in q.dtype, k_pages', v_pages') with exactly
    one ``(page, offset)`` row per slot replaced in each pool (aliased in
    place).  The grid walks slots; a chain-vmapped engine (pools batched,
    tables and positions shared) gets one kernel over a ``(chain, slot)``
    grid.
    """
    S, KV, G, hd = q.shape
    kp, vp, o = _paged_chains(
        jnp.asarray(tables, jnp.int32), jnp.asarray(pos, jnp.int32),
        q.reshape(S, KV * G, hd), k_new.astype(k_pages.dtype),
        v_new.astype(v_pages.dtype), k_pages, v_pages)
    return o.reshape(S, KV, G, hd), kp, vp
