"""Jit'd public wrappers: reshape pytrees into kernel-friendly views.

``fused_langevin_update(params, grads, seed, gamma, scale)`` applies the
fused SGLD update leafwise; ``fused_delay_gather(ring_history, slots)`` does
the W-Icon read; ``fused_decode_step`` / ``fused_paged_decode_step`` put the
decode-step kernels in model layout.  Each kernel chooses compiled or
interpreted mode from the platform it is lowered for
(:func:`repro.kernels.on_backend`).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import delay_gather as dg
from repro.kernels import langevin_update as lu
from repro.utils import round_up

PyTree = Any


def _rows(x: jnp.ndarray) -> jnp.ndarray:
    """The row-major 2-D view ``(prod(leading dims), last dim)`` of a leaf
    (a scalar is ``(1, 1)``, a vector one row) — a reshape, never a pad."""
    if x.ndim == 0:
        return x.reshape(1, 1)
    return x.reshape(-1, x.shape[-1])


def fused_langevin_update(params: PyTree, grads: PyTree, seed, gamma,
                          scale) -> PyTree:
    """Leafwise fused SGLD update with a distinct seed fold per leaf."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    gleaves = jax.tree_util.tree_leaves(grads)
    seed = jnp.asarray(seed, jnp.uint32)
    out = []
    for i, (p, g) in enumerate(zip(leaves, gleaves)):
        leaf_seed = jnp.stack([seed[0] ^ jnp.uint32((0x85EBCA6B * (i + 1)) & 0xFFFFFFFF),
                               seed[1] + jnp.uint32(i)])
        new = lu.langevin_update_2d(_rows(p), _rows(g), leaf_seed, gamma,
                                    scale)
        out.append(new.reshape(p.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def delay_gather_flat(history: jnp.ndarray, slots: jnp.ndarray) -> jnp.ndarray:
    """history: (depth, N) any N; slots: (N,) int32."""
    depth, n = history.shape
    n_pad = max(dg.BLOCK, round_up(n, dg.BLOCK))
    h = jnp.zeros((depth, n_pad), history.dtype).at[:, :n].set(history)
    s = jnp.zeros((n_pad,), jnp.int32).at[:n].set(slots)
    out = dg.delay_gather_1d(h, s)
    return out[:n]


def fused_decode_step(q: jnp.ndarray, k_new: jnp.ndarray, v_new: jnp.ndarray,
                      k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                      valid: jnp.ndarray, slot):
    """Fused streaming decode step in model layout.

    q: (B, H, hd); k_new, v_new: (B, KV, hd); caches: (B, smax, KV, hd);
    valid: (smax,) int32 slot-validity mask (already includes the window and
    the just-written slot); slot: scalar int32 ring slot for the new token.
    Returns (o (B, H, hd), k_cache', v_cache').
    """
    from repro.kernels import decode_step as ds

    B, H, hd = q.shape
    KV = k_cache.shape[2]
    o, kc, vc = ds.decode_step_2d(
        q.reshape(B, KV, H // KV, hd), k_new, v_new, k_cache, v_cache,
        jnp.asarray(valid, jnp.int32), jnp.asarray(slot, jnp.int32).reshape(1))
    return o.reshape(B, H, hd), kc, vc


def fused_paged_decode_step(q: jnp.ndarray, k_new: jnp.ndarray,
                            v_new: jnp.ndarray, k_pages: jnp.ndarray,
                            v_pages: jnp.ndarray, tables: jnp.ndarray,
                            pos: jnp.ndarray):
    """Fused paged decode step in model layout.

    q: (S, H, hd); k_new, v_new: (S, KV, hd); k_pages, v_pages:
    (n_pages, page_size, KV, hd) block pool shared by all slots; tables:
    (S, maxp) int32 per-slot page table; pos: (S,) int32 absolute position
    per slot.  Returns (o (S, H, hd), k_pages', v_pages').
    """
    from repro.kernels import decode_step as ds

    S, H, hd = q.shape
    KV = k_pages.shape[2]
    o, kp, vp = ds.paged_decode_step(
        q.reshape(S, KV, H // KV, hd), k_new, v_new, k_pages, v_pages,
        jnp.asarray(tables, jnp.int32), jnp.asarray(pos, jnp.int32))
    return o.reshape(S, H, hd), kp, vp


def fused_delay_gather(ring_history: PyTree, slots: PyTree, head,
                       depth: int) -> PyTree:
    """W-Icon read over a ring-buffer pytree (leaves (depth, *shape)) with
    per-coordinate delay pytree ``slots`` (leaves shaped like params)."""

    def one(h, s):
        shape = h.shape[1:]
        slot = jnp.mod(head - s.reshape(-1), depth).astype(jnp.int32)
        flat = delay_gather_flat(h.reshape(depth, -1), slot)
        return flat.reshape(shape)

    return jax.tree_util.tree_map(one, ring_history, slots)
