"""Multi-head latent attention (MLA, DeepSeek-V2/V3) for the training and
prefill forward, in the non-absorbed form.

Per token ``h`` (d wide), H heads, no q LoRA (Moonlight, DeepSeek-V2-Lite):

    q            = h @ wq                  -> H x (nope + rope)
    [c, k_pe]    = h @ wkv_a               -> kv_lora_rank + rope
    [k_nope, v]  = rms(c) @ wkv_b          -> H x (nope + v_head_dim)
    q_pe, k_pe   = rope(q_pe), rope(k_pe)  (k_pe one head, shared by all)
    o            = softmax([q_nope, q_pe] . [k_nope, k_pe] / sqrt(nope + rope)) v
    y            = o @ wo

The latent's RMSNorm is ``kv_a_layernorm``, which the published modeling
code builds with its module default eps 1e-6, not ``rms_norm_eps``.

Rope follows the published DeepseekV3 convention, so that published weights
load unchanged: the 64 rope dims of ``q_proj`` and ``kv_a_proj_with_mqa``
come as interleaved pairs ``(x0, x1), (x2, x3), ...``; they are permuted to
halves ``(x0, x2, ..., x1, x3, ...)`` and then rotated half against half
(:func:`repro.models.common.apply_rope`), with inverse frequencies
``theta^(-2i/64)``.

Parameter layout (``in x out``, as every projection here): ``wq`` (d, H
(nope + rope)), ``wkv_a`` (d, rank + rope), ``kv_norm`` (rank,), ``wkv_b``
(rank, H (nope + v)), ``wo`` (H v, d) — HF's ``q_proj``,
``kv_a_proj_with_mqa``, ``kv_a_layernorm``, ``kv_b_proj`` and ``o_proj``,
transposed.  Serving MLA needs a latent paged cache, which this module
does not have: the decode paths refuse an MLA model.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.attention import attention_any
from repro.models.common import apply_rope, dense_init, rms_norm

#: eps of the latent's RMSNorm (DeepseekV3RMSNorm's default)
LATENT_EPS = 1e-6


def init_mla(key, cfg, dtype) -> dict:
    d, H = cfg.d_model, cfg.num_heads
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, H * (nope + rope)), dtype),
        "wkv_a": dense_init(ks[1], (d, r + rope), dtype),
        "kv_norm": jnp.ones((r,), jnp.float32),
        "wkv_b": dense_init(ks[2], (r, H * (nope + dv)), dtype),
        "wo": dense_init(ks[3], (H * dv, d), dtype,
                         scale=1.0 / math.sqrt(H * dv * 2 * cfg.num_layers)),
    }


def rope_halves(x: jnp.ndarray) -> jnp.ndarray:
    """Interleaved rope pairs ``(x0, x1), (x2, x3), ...`` of the last axis
    permuted to halves ``(x0, x2, ..., x1, x3, ...)``."""
    *lead, r = x.shape
    return x.reshape(*lead, r // 2, 2).swapaxes(-1, -2).reshape(*lead, r)


def apply_mla(p, x, cfg, positions) -> jnp.ndarray:
    """x: (B, S, d) -> (B, S, d), causal."""
    B, S, _ = x.shape
    H = cfg.num_heads
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    with jax.named_scope("mla"):
        q = (x @ p["wq"]).reshape(B, S, H, nope + rope)
        ckv = x @ p["wkv_a"]
        c = rms_norm(ckv[..., :r], p["kv_norm"], LATENT_EPS)
        kv = (c @ p["wkv_b"]).reshape(B, S, H, nope + dv)
        q_pe = apply_rope(rope_halves(q[..., nope:]), positions,
                          cfg.rope_theta)
        k_pe = apply_rope(rope_halves(ckv[..., None, r:]), positions,
                          cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rope))], axis=-1)
        o = attention_any(q, k, kv[..., nope:], causal=True)
        return o.reshape(B, S, H * dv) @ p["wo"]
