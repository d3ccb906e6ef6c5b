"""Small shared utilities: pytree helpers, key handling, shape math."""

from __future__ import annotations

import math
import os
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any

#: where :func:`enable_compile_cache` keeps compiled programs when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed directory of the checkout
#: (listed in ``.gitignore``), so every run from it finds the same cache
COMPILE_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, names the directory (JAX reads
    it itself, and no other path is set here); otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`.  Entry points call this; importing the
    library never does."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def tree_keys(key: jax.Array, tree: PyTree) -> PyTree:
    """Split `key` into one independent key per leaf of `tree`."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(treedef, list(keys))


def tree_normal_like(key: jax.Array, tree: PyTree, dtype=None) -> PyTree:
    """A pytree of iid standard normals shaped like `tree`."""
    keytree = tree_keys(key, tree)
    return jax.tree_util.tree_map(
        lambda k, x: jax.random.normal(k, jnp.shape(x), dtype or jnp.result_type(x)),
        keytree,
        tree,
    )


def tree_add_scaled(a: PyTree, b: PyTree, scale) -> PyTree:
    """a + scale * b, leafwise."""
    return jax.tree_util.tree_map(lambda x, y: x + scale * y, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree_util.tree_map(lambda x, y: x - y, a, b)


def tree_scale(a: PyTree, scale) -> PyTree:
    return jax.tree_util.tree_map(lambda x: scale * x, a)


def tree_zeros_like(a: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.zeros_like, a)


def tree_broadcast_leading(a: PyTree, n: int) -> PyTree:
    """Replicate every leaf along a new materialized leading axis of size
    ``n`` (ring-buffer history slots, ensemble chain axes)."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n,) + jnp.shape(x)).copy(), a)


def tree_dot(a: PyTree, b: PyTree) -> jax.Array:
    parts = jax.tree_util.tree_map(lambda x, y: jnp.vdot(x, y), a, b)
    return jax.tree_util.tree_reduce(jnp.add, parts, jnp.float32(0.0))


def tree_sq_norm(a: PyTree) -> jax.Array:
    return tree_dot(a, a)


def tree_size(a: PyTree) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(a))


def tree_ravel(a: PyTree) -> jax.Array:
    """Flatten a pytree into a single 1-D vector (float32)."""
    leaves = jax.tree_util.tree_leaves(a)
    return jnp.concatenate([jnp.ravel(x).astype(jnp.float32) for x in leaves])


def bucket_size(n: int, buckets=None) -> int:
    """Smallest bucket ladder rung holding ``n`` items: the next power of two,
    or the smallest entry of an explicit ``buckets`` ladder (which is a
    contract — ``n`` larger than the top rung fails loudly instead of
    silently extending the ladder).  Shared by the serve request batcher and
    the heterogeneous-minibatch schedule compiler so both compile one trace
    per rung, never one per size."""
    if n < 1:
        raise ValueError(f"need at least one item, got {n}")
    if buckets is None:
        return 1 << (n - 1).bit_length()
    fits = [b for b in buckets if b >= n]
    if not fits:
        raise ValueError(f"{n} items exceed the largest bucket "
                         f"{max(buckets)}; pass a deeper `buckets` ladder")
    return min(fits)


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}PiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.3g}{unit}"
        n /= 1000.0
    return f"{n:.3g}Q"


def gaussian_log_density(x: jax.Array, mean: jax.Array, cov_diag: jax.Array) -> jax.Array:
    d = x.shape[-1]
    quad = jnp.sum((x - mean) ** 2 / cov_diag, axis=-1)
    logdet = jnp.sum(jnp.log(cov_diag))
    return -0.5 * (quad + logdet + d * math.log(2.0 * math.pi))
