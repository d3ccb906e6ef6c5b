"""Shared model components: norms, rotary embeddings, init, sharding rules.

Parameters are plain nested dicts.  Sharding is derived from *leaf path
names* (t5x-style logical rules): see ``partition_rules``.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PyTree = Any


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------
def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dt)


def head_rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """Per-head qk-norm: x (..., H, hd), scale (hd,)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dt)


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def dense_init(key, shape, dtype, scale: float | None = None) -> jnp.ndarray:
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def embed_init(key, shape, dtype) -> jnp.ndarray:
    return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# sharding rules: leaf-path regexp-free suffix matching
# ---------------------------------------------------------------------------
# Each rule: (path_suffix, PartitionSpec). First match wins. "mdl" = tensor
# axis, "fsdp_axes" used only under param_sharding == "fsdp_tp".
def partition_rules(param_sharding: str, fsdp_axes=("data",), cfg=None,
                    model_size: int | None = None):
    mdl = "model"
    fsdp = fsdp_axes  # secondary axes for trillion-scale 2-D sharding
    two_d = param_sharding == "fsdp_tp"
    # Never shard an attention projection finer than its head boundary:
    # splitting one head's head_dim across devices forces cross-shard
    # resharding inside rope/norm/attention (and miscompiles on some XLA
    # CPU builds).  Unknown cfg/model_size keeps the legacy always-shard
    # rule for backward compatibility.
    q_head_ok = bool(cfg is None or not model_size
                     or cfg.num_heads % model_size == 0)
    kv_head_ok = bool(cfg is None or not model_size
                      or cfg.num_kv_heads % model_size == 0)
    wq_spec = P(None, mdl) if q_head_ok else P(None, None)
    wo_spec = P(mdl, None) if q_head_ok else P(None, None)
    kv_spec = P(None, mdl) if kv_head_ok else P(None, None)
    kvb_spec = P(mdl) if kv_head_ok else P(None)
    qb_spec = P(mdl) if q_head_ok else P(None)
    rules = [
        # embeddings / head
        ("embed/w", P(mdl, None)),
        ("lm_head/w", P(None, mdl)),
        # attention
        ("attn/wq", wq_spec),
        ("attn/wk", kv_spec),
        ("attn/wv", kv_spec),
        ("attn/wo", wo_spec),
        ("attn/bq", qb_spec),
        ("attn/bk", kvb_spec),
        ("attn/bv", kvb_spec),
        ("attn/q_norm", P(None)),
        ("attn/k_norm", P(None)),
        # dense mlp
        ("mlp/w_gate", P(None, mdl)),
        ("mlp/w_up", P(None, mdl)),
        ("mlp/w_down", P(mdl, None)),
        # moe: experts over model axis; optionally d_ff over data axis (2-D)
        ("moe/w_gate", P(mdl, None, fsdp if two_d else None)),
        ("moe/w_up", P(mdl, None, fsdp if two_d else None)),
        ("moe/w_down", P(mdl, fsdp if two_d else None, None)),
        ("moe/router", P(None, None)),
        ("moe/shared_w_gate", P(None, mdl)),
        ("moe/shared_w_up", P(None, mdl)),
        ("moe/shared_w_down", P(mdl, None)),
        # mamba / hymba ssm heads
        ("ssm/in_proj", P(None, mdl)),
        ("ssm/conv_w", P(mdl, None)),
        ("ssm/dt_w", P(None, mdl)),
        ("ssm/dt_bias", P(mdl)),
        ("ssm/bc_proj", P(None, None)),
        ("ssm/a_log", P(mdl)),
        ("ssm/d_skip", P(mdl)),
        ("ssm/out_proj", P(mdl, None)),
        # xlstm
        # xLSTM blocks are batch-parallel with replicated params (§Perf
        # pair-4): every TP layout tried (column-TP baseline, dv-sharded
        # state) makes GSPMD reshard the (B,S,H,dk) <-> (B,S,di) views at
        # each layer (45s / 185s of collective vs 34s replicated).  The
        # right TP for matrix-state recurrences is a hand-written shard_map
        # (as done for MoE) — documented future work.
        ("mlstm/", P(None)),
        ("slstm/", P(None)),
        # frontend projector stub
        ("frontend/proj", P(None, mdl)),
        # norms & everything 1-D replicated
        ("norm", P(None)),
    ]
    return rules


def spec_for_path(path: str, rules) -> P:
    for suffix, spec in rules:
        if suffix in path:
            return spec
    return P()  # replicate


def partition_tree(params: PyTree, param_sharding: str = "tp",
                   fsdp_axes=("data",), cfg=None,
                   model_size: int | None = None) -> PyTree:
    """PartitionSpec pytree matching ``params`` by leaf path."""
    rules = partition_rules(param_sharding, fsdp_axes, cfg, model_size)

    def visit(kp, leaf):
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
        spec = spec_for_path(path, rules)
        # stacked-layer params carry a leading L axis -> prepend None
        ndim = getattr(leaf, "ndim", len(getattr(leaf, "shape", ())))
        if len(spec) < ndim and "/stack/" in "/" + path + "/":
            spec = P(*((None,) + tuple(spec)))
        if len(spec) > ndim:
            spec = P(*spec[:ndim])
        return spec

    return jax.tree_util.tree_map_with_path(visit, params)
