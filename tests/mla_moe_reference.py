"""Plain float32 reference of a DeepSeek-V3 decoder (Moonlight-16B-A3B's
block) for the CPU tests, written from the published modeling code
(``modeling_deepseek.py``: ``DeepseekV3Attention``, ``MoEGate``,
``DeepseekV3MoE``, ``DeepseekV3MLP``) and sharing no code with ``repro``.

Every matmul is float32 at ``highest`` precision.  Experts are computed
densely: each held expert runs on every token and its output counts where
the gate chose it (no sorting, no grouped matmul).  It reads a parameter
tree in the program's layout (``in x out`` matrices; ``lead`` dense layers,
then the ``stack`` of MoE layers) and takes the configuration as an
``ArchConfig``-like object for its numbers only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def mm(x, w):
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=HI)


def rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(jnp.float32)


def rotary(x, theta):
    """``apply_rotary_pos_emb`` of DeepseekV3 on x (B, S, h, r): the
    interleaved pairs viewed as (r/2, 2) and transposed, then
    ``x cos + rotate_half(x) sin`` with ``emb = cat(freqs, freqs)``."""
    B, S, h, r = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    freqs = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([freqs, freqs], -1)
    cos, sin = jnp.cos(emb)[None, :, None], jnp.sin(emb)[None, :, None]
    x = x.reshape(B, S, h, r // 2, 2).transpose(0, 1, 2, 4, 3).reshape(
        B, S, h, r)
    rot = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * cos + rot * sin


def mla(p, h, cfg):
    """DeepseekV3Attention with ``q_lora_rank`` None, causal."""
    B, S, _ = h.shape
    H, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    r = cfg.kv_lora_rank
    q = mm(h, p["wq"]).reshape(B, S, H, dn + dr)
    ckv = mm(h, p["wkv_a"])
    kv = mm(rms(ckv[..., :r], p["kv_norm"], 1e-6), p["wkv_b"]).reshape(
        B, S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], cfg.rope_theta)],
                        -1)
    k_pe = rotary(ckv[..., None, r:], cfg.rope_theta)
    k = jnp.concatenate([kv[..., :dn], jnp.repeat(k_pe, H, axis=2)], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * \
        (dn + dr) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kv[..., dn:],
                   precision=HI)
    return mm(o.reshape(B, S, H * dv), p["wo"])


def swiglu(h, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def gate(h, router, bias, cfg):
    """MoEGate, ``noaux_tc``: (T, E) weights, zero where not chosen."""
    scores = jax.nn.sigmoid(mm(h, router))
    _, idx = jax.lax.top_k(scores + bias[None], cfg.experts_per_token)
    chosen = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1]), axis=1) > 0
    w = jnp.where(chosen, scores, 0.0)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor


def moe(p, h, bias, cfg, held=None, offset=0, shared=True):
    """DeepseekV3MoE on h (B, S, d): the experts ``offset ..
    offset + held - 1`` (p's expert rows 0 ..), plus the shared experts."""
    B, S, d = h.shape
    x = h.reshape(B * S, d)
    w = gate(x, p["router"], bias, cfg)
    held = p["w_gate"].shape[0] if held is None else held
    y = jnp.zeros((B * S, d), jnp.float32)
    for e in range(held):
        out = swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        y = y + w[:, offset + e, None] * out
    if shared:
        y = y + swiglu(x, p["shared_w_gate"], p["shared_w_up"],
                       p["shared_w_down"])
    return y.reshape(B, S, d)


def loads(p, h, bias, cfg, held, offset=0):
    """Tokens routed to each held expert."""
    x = h.reshape(-1, h.shape[-1])
    w = gate(x, p["router"], bias, cfg)
    return jnp.sum(w[:, offset:offset + held] > 0, axis=0)


def layer(p, x, cfg, bias=None):
    """One pre-norm layer: MLA, then the dense MLP or the MoE."""
    x = x + mla(p["attn"], rms(x, p["norm1"], cfg.norm_eps), cfg)
    h = rms(x, p["norm2"], cfg.norm_eps)
    if "mlp" in p:
        m = p["mlp"]
        return x + swiglu(h, m["w_gate"], m["w_up"], m["w_down"])
    return x + moe(p["moe"], h, bias, cfg, offset=cfg.expert_offset)


def loss(params, tokens, cfg, biases):
    """Mean next-token cross-entropy over tokens (B, S+1); ``biases`` (moe
    layers, E)."""
    x = jnp.take(params["embed"]["w"], tokens[:, :-1], axis=0).astype(
        jnp.float32)
    for p in params["lead"]:
        x = layer(p, x, cfg)
    n = jax.tree_util.tree_leaves(params["stack"])[0].shape[0]
    for i in range(n):
        p = jax.tree_util.tree_map(lambda a, i=i: a[i], params["stack"])
        x = layer(p, x, cfg, biases[i])
    logits = mm(rms(x, params["final_norm"], cfg.norm_eps),
                params["lm_head"]["w"])
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))
