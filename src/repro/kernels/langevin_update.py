"""Pallas TPU kernel: fused SGLD update  x <- x - gamma*g + sqrt(2*sigma*gamma)*xi.

The paper's per-iterate hot path touches every parameter once; unfused, XLA
emits (RNG -> HBM), (read x, g, noise -> write x'): three HBM round trips of
the full parameter vector.  This kernel generates the Langevin noise *in
VMEM* (counter-based threefry, rng.py) and fuses the update: one read of
(x, g), one write of x'.

Layout: any 2-D view ``(R, C)`` of a parameter leaf in its own dtype (ops.py
passes ``(prod(leading dims), last dim)``, so nothing is padded or cast in
HBM).  The grid walks ``(256, 1024)`` blocks — 1 MiB per operand in fp32,
double-buffered well inside the default scoped VMEM — and Pallas masks a
partial edge block.  The noise counter of element ``(r, c)`` is its
row-major index ``r*C + c``, the flat index of the leaf, so the noise does
not depend on the view or the blocking.  The arithmetic is fp32 whatever
the leaf dtype; the result is rounded to the leaf dtype once.  The seed
and the two scalars arrive by scalar prefetch (SMEM).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import on_backend
from repro.kernels.rng import normal_from_counter

BLOCK_ROWS = 256
LANES = 1024  # block columns


def _kernel(seed_ref, coef_ref, x_ref, g_ref, o_ref, *, width: int):
    rows, cols = x_ref.shape
    i, j = pl.program_id(0), pl.program_id(1)
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) + i * rows
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1) + j * cols
    xi = normal_from_counter(seed_ref[0], seed_ref[1], r * width + c)
    gamma, scale = coef_ref[0], coef_ref[1]
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    o_ref[...] = (x - gamma * g + scale * xi).astype(o_ref.dtype)


def _call(x, g, seed, coef, *, interpret: bool):
    R, C = x.shape
    br, bc = min(R, BLOCK_ROWS), min(C, LANES)
    block = pl.BlockSpec((br, bc), lambda i, j, *_: (i, j))
    return pl.pallas_call(
        partial(_kernel, width=C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(pl.cdiv(R, br), pl.cdiv(C, bc)),
            in_specs=[block, block], out_specs=block),
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        # the update overwrites x block-for-block: alias it so XLA reuses
        # the buffer instead of holding a second copy of the leaf
        input_output_aliases={2: 0},
        interpret=interpret,
        name="langevin_update",
    )(seed, coef, x, g)


@jax.jit
def langevin_update_2d(x, g, seed: jnp.ndarray, gamma, scale):
    """x, g: (R, C) of one float dtype, R*C <= 2^32; seed: (2,) uint32.

    Returns x - gamma*g + scale*xi in x's dtype, xi[r, c] the standard
    normal of counter ``r*C + c`` under ``seed``.
    """
    R, C = x.shape
    if g.shape != x.shape or R * C > 1 << 32:
        raise ValueError(f"bad shapes x {x.shape}, g {g.shape}")
    seed = jax.lax.bitcast_convert_type(jnp.asarray(seed, jnp.uint32),
                                        jnp.int32)
    coef = jnp.stack([jnp.asarray(gamma, jnp.float32),
                      jnp.asarray(scale, jnp.float32)])
    return on_backend(_call, x, g.astype(x.dtype), seed, coef)
