"""Synthetic data: token streams and frontend-embedding stubs.

``make_batch`` returns real arrays for an (arch, shape) pair: the
launcher's batches, the smoke tests' and the examples'.

Frontend stubs (the one allowed stub): VLM batches carry precomputed patch
embeddings, audio batches carry precomputed frame embeddings, both of width
``FRONTEND_DIM`` — standing in for InternViT / EnCodec outputs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.transformer import FRONTEND_DIM


def _text_len(cfg, shape) -> int:
    n_front = cfg.num_frontend_tokens if cfg.frontend else 0
    return shape.seq_len - n_front


def token_stream(key, vocab_size: int, batch: int, length: int) -> jnp.ndarray:
    """Markov-ish synthetic tokens (not uniform — gives a learnable signal)."""
    k1, k2 = jax.random.split(key)
    base = jax.random.randint(k1, (batch, length), 0, vocab_size, dtype=jnp.int32)
    # repeat-previous structure: ~50% of positions copy position-1
    rep = jax.random.bernoulli(k2, 0.5, (batch, length))
    shifted = jnp.roll(base, 1, axis=1)
    return jnp.where(rep, shifted, base)


def make_batch(cfg, shape, key, kind: str | None = None):
    """Real arrays for a (arch, shape) pair. kind defaults to shape.kind."""
    kind = kind or shape.kind
    B = shape.global_batch
    kf, kt = jax.random.split(key)

    if kind in ("train", "prefill"):
        s_text = _text_len(cfg, shape)
        extra = 1 if kind == "train" else 0
        batch = {"tokens": token_stream(kt, cfg.vocab_size, B, s_text + extra)}
        if cfg.frontend:
            batch["frontend"] = jax.random.normal(
                kf, (B, cfg.num_frontend_tokens, FRONTEND_DIM), jnp.float32)
        return batch

    if kind == "decode":
        return {"tokens": jax.random.randint(kt, (B, 1), 0, cfg.vocab_size,
                                             dtype=jnp.int32),
                "cur_pos": jnp.int32(shape.seq_len - 1)}
    raise ValueError(kind)

