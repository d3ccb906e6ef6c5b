"""Pallas TPU kernel: fused SGLD update  x <- x - gamma*g + sqrt(2*sigma*gamma)*xi.

The paper's per-iterate hot path touches every parameter once; unfused, XLA
emits (RNG -> HBM), (read x, g, noise -> write x'): three HBM round trips of
the full parameter vector.  This kernel generates the Langevin noise *in
VMEM* (counter-based threefry, rng.py) and fuses the update: one read of
(x, g), one write of x'.

Layout: any 2-D view ``(R, C)`` of a parameter leaf in its own dtype (ops.py
passes ``(prod(leading dims), last dim)``, so nothing is padded or cast in
HBM).  The noise counter of element ``(r, c)`` is its row-major index
``r*C + c``, the flat index of the leaf, so the noise does not depend on
the view or the blocking.  The arithmetic is fp32 whatever the leaf dtype;
the result is rounded to the leaf dtype once.  The seed and the two scalars
arrive by scalar prefetch (SMEM).

Blocking (:func:`tiling`).  Where C is a multiple of 128 the block is as
wide as the widest multiple of 128 up to 1280 that divides C (1280 for
2560, 1024 for 4096, 512 for 9728), so no computed lane is masked away;
any other width keeps a block of C or 1024 columns and a masked edge.  A
block holds about 320 f32 vregs (1.25 MiB an operand in fp32,
double-buffered inside the default scoped VMEM).  The kernel walks a block
in row strips of at most 16 vregs (8 x 1280, 16 x 1024, 32 x 512): a
strip's 20-round threefry, Box-Muller and update stay in vector registers,
where a whole block's intermediates would spill to VMEM, and the strip
loop runs near the VALU's bound (strips of 20 vregs schedule as tightly
in the compiler's dump but ran 8% slower on a TPU v5e).  The last row
block stops after the strip that holds the view's last row.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import on_backend
from repro.kernels.rng import normal_from_counter
from repro.utils import round_up

LANE = 128
WIDEST = 1280  # widest block of a view whose width is a multiple of LANE
FALLBACK_COLS = 1024  # block width of any other view
STRIP = 16 * 8 * LANE  # f32 elements of one strip: 16 vregs
BLOCK = 320 * 8 * LANE  # f32 elements of one block: 320 vregs


def tiling(R: int, C: int) -> tuple[int, int, int]:
    """``(block rows, block cols, strip rows)`` of an ``(R, C)`` view.

    The block's width divides C when C is a multiple of 128, so no block
    is masked at the right edge; otherwise it is C or 1024.  A strip is a
    power of two of rows, at least 8 (one f32 vreg's), holding at most
    ``STRIP`` elements of the lane-padded width; the block's rows are a
    multiple of it, or the whole view when the view has fewer rows.
    """
    if C % LANE == 0:
        bc = max(w for w in range(LANE, min(C, WIDEST) + 1, LANE)
                 if C % w == 0)
    else:
        bc = min(C, FALLBACK_COLS)
    padded = round_up(bc, LANE)
    sr = 8
    while 2 * sr * padded <= STRIP:
        sr *= 2
    if R <= sr:
        return R, bc, R
    return min(BLOCK // padded, R) // sr * sr, bc, sr


def _kernel(seed_ref, coef_ref, x_ref, g_ref, o_ref, *, height: int,
            width: int, strip: int):
    rows, cols = x_ref.shape
    row0 = pl.program_id(0) * rows
    col0 = pl.program_id(1) * cols
    seed0, seed1 = seed_ref[0], seed_ref[1]
    gamma, scale = coef_ref[0], coef_ref[1]

    # one strip at a time, so the threefry -> Box-Muller -> update chain
    # of a strip stays in vector registers instead of spilling to VMEM
    def body(s, carry):
        top = pl.multiple_of(s * strip, strip)
        r = jax.lax.broadcasted_iota(jnp.int32, (strip, cols), 0) + row0 + top
        c = jax.lax.broadcasted_iota(jnp.int32, (strip, cols), 1) + col0
        xi = normal_from_counter(seed0, seed1, r * width + c)
        at = (pl.ds(top, strip), slice(None))
        x = x_ref[at].astype(jnp.float32)
        g = g_ref[at].astype(jnp.float32)
        o_ref[at] = (x - gamma * g + scale * xi).astype(o_ref.dtype)
        return carry

    # the last row block stops at the strip that holds the view's last row
    jax.lax.fori_loop(0, jnp.minimum(rows // strip,
                                     pl.cdiv(height - row0, strip)), body, 0)


def _call(x, g, seed, coef, *, interpret: bool):
    R, C = x.shape
    br, bc, sr = tiling(R, C)
    block = pl.BlockSpec((br, bc), lambda i, j, *_: (i, j))
    return pl.pallas_call(
        partial(_kernel, height=R, width=C, strip=sr),
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
