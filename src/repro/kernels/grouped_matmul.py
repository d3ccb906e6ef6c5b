"""Grouped matmul over the experts a device holds (dropless MoE).

``grouped_matmul(lhs, rhs, group_sizes)``: the rows of ``lhs`` (m, k) come
sorted by group, ``group_sizes[g]`` rows for ``rhs[g]`` (g, k, n), and each
group's rows are multiplied by its own matrix; rows past the last group are
zero in the result.  The kernels are the installed Pallas megablox ``gmm``
(forward and the input gradient) and ``tgmm`` (the weight gradient), with
their custom VJP.  Only the row tiles that hold a group's rows are
computed: the grid's length is read from the group sizes on the device,
so a buffer sized for the worst case (every token routed here) costs its
memory, not its FLOPs.

The megablox kernels are named ``gmm`` and ``tgmm`` by their module's
functions; the calls carry their operands (three int32 group-metadata
vectors and the int32 group offset, then lhs and rhs) in front, which is
how a trace finds them.  Each call picks compiled or interpreted mode from
the platform it is lowered for (:func:`repro.kernels.on_backend`).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.kernels import on_backend
from repro.utils import round_up

TM = 512  # rows of a tile


def tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` of one call: 512-row tiles; the contracted and the
    output width in 512-wide tiles where they divide by 512, else whole
    (1408, an expert's width, is 11 lanes of 128 and no multiple of 512).
    Every tile set double-buffered stays under 10 MiB of VMEM."""
    return (min(TM, m), 512 if k % 512 == 0 else k,
            512 if n % 512 == 0 else n)


def _gmm(lhs, rhs, sizes, *, interpret: bool):
    return megablox.gmm(lhs, rhs, sizes, lhs.dtype, tiling,
                        None, None, False, interpret)


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray) -> jnp.ndarray:
    """lhs (m, k), rhs (g, k, n), group_sizes (g,) int32 -> (m, n) in lhs's
    dtype (float32 accumulation); differentiable in lhs and rhs."""
    m = lhs.shape[0]
    tm = min(TM, round_up(m, 8))
    mp = round_up(m, tm)
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    # one more group for the rows past the held groups: the kernel then
    # computes nothing there and zeroes them
    sizes = jnp.concatenate([group_sizes.astype(jnp.int32),
                             (mp - jnp.sum(group_sizes)).astype(jnp.int32)[None]])
    out = on_backend(_gmm, lhs, rhs.astype(lhs.dtype), sizes)
    return out[:m]

