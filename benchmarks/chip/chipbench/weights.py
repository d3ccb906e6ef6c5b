"""Weights and token data made on the device from the seed.

The weights are a parameter tree in the program's layout (read from its
``init_params`` by shape only, never run), filled by the benchmark in one
jitted call: matrices normal with standard deviation 1/sqrt(fan-in) (the
attention output 1/sqrt(2 L q_dim), as deep pre-norm stacks are
initialised), embeddings 0.02, norm scales 1 + 0.1 normal so that a norm
weight the program skipped would show.  Tokens are uniform over the
vocabulary.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.harness import base_key


def layout(cfg):
    """``jax.ShapeDtypeStruct`` tree of one chain's parameters."""
    from repro.models.transformer import init_params

    return jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def _std(path: str, shape, cfg) -> float:
    if "embed" in path:
        return 0.02
    if path.endswith("attn/wo"):
        return 1.0 / math.sqrt(2 * cfg.num_layers * cfg.q_dim)
    return 1.0 / math.sqrt(shape[-2])


def make_weights(cfg, seed: int):
    """One chain's parameters, in one jitted call."""
    shapes = layout(cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]

    def one(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for path, (_, sds), k in zip(paths, flat, keys):
            z = jax.random.normal(k, sds.shape, jnp.float32)
            if "norm" in path.rsplit("/", 1)[-1]:
                leaf = 1.0 + 0.1 * z
            else:
                leaf = _std(path, sds.shape, cfg) * z
            out.append(leaf.astype(sds.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(one)(jax.random.fold_in(base_key(seed), 0x5745))


def make_tokens(seed: int, shape, vocab: int, tag: int):
    """Uniform token ids of ``shape`` from the seed (one jitted call)."""
    key = jax.random.fold_in(base_key(seed), tag)
    return jax.jit(lambda k: jax.random.randint(k, shape, 0, vocab,
                                                jnp.int32))(key)
