"""Pallas TPU kernel: per-coordinate stale read (the W-Icon hot path).

Gathers x_hat[i] = history[(head - delay_i) mod depth, i] from the ring
buffer.  A naive take_along_axis materializes the flattened index arithmetic
in HBM; this kernel streams one (depth, BLOCK) VMEM tile of history per
output block and reduces the slot-select on chip:

    out = sum_d history[d, :] * (d == slot)

which is a (depth x BLOCK) broadcast-compare + multiply-reduce — ideal VPU
shape since depth = tau+1 is small (<= 8 in fidelity runs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import on_backend

BLOCK = 4096  # lanes per grid step (32 sublanes x 128 lanes fp32)


def _kernel(hist_ref, slot_ref, o_ref):
    depth, blk = hist_ref.shape
    d_ids = jax.lax.broadcasted_iota(jnp.int32, (depth, blk), 0)
    sel = (d_ids == slot_ref[...][None, :]).astype(hist_ref.dtype)
    o_ref[...] = jnp.sum(hist_ref[...] * sel, axis=0)


def _call(history, slots, *, interpret: bool):
    depth, N = history.shape
    grid = (N // BLOCK,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((depth, BLOCK), lambda i: (0, i)),
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((BLOCK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((N,), history.dtype),
        interpret=interpret,
        name="delay_gather",
    )(history, slots)


@jax.jit
def delay_gather_1d(history, slots):
    """history: (depth, N) float32; slots: (N,) int32 in [0, depth).
    N % BLOCK == 0.  Returns (N,) gathered values."""
    if history.shape[1] % BLOCK:
        raise ValueError(f"N={history.shape[1]} is not a multiple of {BLOCK}")
    return on_backend(_call, history, slots)
