"""Counter-based RNG (threefry2x32) + Box-Muller, in plain jnp ops.

Used *inside* the Pallas langevin_update kernel (plain jnp lowers fine in
kernels) and by the pure-jnp oracle in ref.py — so kernel and oracle are
bit-identical by construction.  Counter = global element index, key = user
seed: reproducible regardless of block shape or sharding.

Every function works on int32 or uint32 words alike and gives the same
bits for both: the shifts are logical and the additions wrap.  The kernel
uses int32, because Mosaic converts int32 to float32 but not uint32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = 0x1BD11BDA  # python ints: jnp constants must be created in-trace
_GOLDEN = 0x9E3779B9  # (pallas kernels reject closure-captured arrays)


def _word(value: int, like) -> jnp.ndarray:
    """The 32-bit word ``value`` in ``like``'s dtype (int32 wraps it)."""
    if like.dtype == jnp.int32 and value >= 1 << 31:
        value -= 1 << 32
    return jnp.asarray(value, like.dtype)


def _shr(x, r: int):
    return jax.lax.shift_right_logical(x, jnp.full_like(x, r))


def _rotl(x, r: int):
    return jax.lax.shift_left(x, jnp.full_like(x, r)) | _shr(x, 32 - r)


def threefry2x32(key0, key1, x0, x1):
    """20-round threefry2x32 (same schedule as JAX's reference) on int32 or
    uint32 words; the keys take the counters' dtype."""
    k0 = jnp.asarray(key0).astype(x0.dtype)
    k1 = jnp.asarray(key1).astype(x0.dtype)
    k2 = k0 ^ k1 ^ _word(_PARITY, x0)
    ks = (k0, k1, k2)

    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for block in range(5):
        rots = _ROTATIONS[block % 2]
        for r in rots:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + _word(block + 1, x1)
    return x0, x1


def uniform_from_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """32-bit word -> float32 uniform in (0, 1): top 24 bits, offset by
    2^-25.  The 24-bit value is exact as int32, so the float conversion is
    a signed one."""
    u = _shr(bits, 8).astype(jnp.int32).astype(jnp.float32)
    return u * jnp.float32(2**-24) + jnp.float32(2**-25)


def normal_from_counter(seed0, seed1, counter: jnp.ndarray) -> jnp.ndarray:
    """Standard normals from int32/uint32 element counters (Box-Muller).

    counter: any-shape int32 or uint32 global element index (pairs share
    bits); other integer dtypes are taken as uint32.
    """
    c = counter
    if c.dtype not in (jnp.int32, jnp.uint32):
        c = c.astype(jnp.uint32)
    b0, b1 = threefry2x32(seed0, seed1, c, c ^ _word(_GOLDEN, c))
    u1 = uniform_from_bits(b0)
    u2 = uniform_from_bits(b1)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(jnp.float32(2.0 * 3.14159265358979) * u2)
