"""Mixture-of-Experts FFN with expert parallelism.

Design (DESIGN.md §5): experts are sharded over the ``model`` mesh axis
(2-D ``fsdp_tp`` additionally shards d_ff over ``data`` and all-gathers per
layer, FSDP-style).  Token dispatch is scatter-based (sort-free GShard-style
capacity buffers) inside ``shard_map``: every device routes its local tokens,
keeps the pairs destined to its local experts, and the final psum over the
``model`` axis combines disjoint expert contributions together with the
column-sharded shared-expert partials.  No dense (T, E, C) dispatch tensor is
ever materialized.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import activation, dense_init

CAPACITY_FACTOR = 1.25


def init_moe(key, cfg, dtype) -> dict:
    E, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 7)
    params = {
        "router": dense_init(ks[0], (d, E), jnp.float32),
        "w_gate": dense_init(ks[1], (E, d, f), dtype),
        "w_up": dense_init(ks[2], (E, d, f), dtype),
        "w_down": dense_init(ks[3], (E, f, d), dtype),
    }
    if cfg.num_shared_experts > 0:
        fs = f * cfg.num_shared_experts
        params["shared_w_gate"] = dense_init(ks[4], (d, fs), dtype)
        params["shared_w_up"] = dense_init(ks[5], (d, fs), dtype)
        params["shared_w_down"] = dense_init(ks[6], (fs, d), dtype)
    return params


def capacity(tokens_local: int, cfg) -> int:
    c = math.ceil(tokens_local * cfg.experts_per_token / cfg.num_experts
                  * CAPACITY_FACTOR)
    return max(4, min(c, tokens_local))


def _moe_local(params, xt, cfg, e_local: int, e_offset, cap: int, act):
    """Route/dispatch/compute for the local expert slice.

    xt: (T, d) local tokens; returns (out (T, d) partial, aux loss scalar).
    """
    T, d = xt.shape
    k = cfg.experts_per_token
    E = cfg.num_experts

    logits = xt.astype(jnp.float32) @ params["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, k)  # (T, k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)

    flat_e = idx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = vals.reshape(-1)

    le = flat_e - e_offset  # local expert index; OOB handled by mode=drop/fill
    in_range = (le >= 0) & (le < e_local)
    le_safe = jnp.where(in_range, le, e_local)  # e_local row is OOB for buffers
    oh = jax.nn.one_hot(le_safe, e_local + 1, dtype=jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(oh, axis=0), le_safe[:, None], axis=1)[:, 0] - 1

    buf = jnp.zeros((e_local, cap, d), xt.dtype)
    buf = buf.at[le_safe, rank].add(xt[flat_t], mode="drop")

    h = act(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", buf, params["w_up"])
    out_e = jnp.einsum("ecf,efd->ecd", h, params["w_down"])

    vals_back = out_e.at[le_safe, rank].get(mode="fill", fill_value=0)  # (T*k, d)
    out = jnp.zeros((T, d), xt.dtype)
    out = out.at[flat_t].add((flat_w[:, None] * vals_back.astype(jnp.float32)
                              ).astype(xt.dtype))

    # Switch-style load-balance aux (computed on full router output).
    frac_tokens = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return out, aux


def _shared_partial(params, xt, act):
    if "shared_w_gate" not in params:
        return 0.0
    h = act(xt @ params["shared_w_gate"]) * (xt @ params["shared_w_up"])
    return h @ params["shared_w_down"]


def apply_moe(params, x, cfg, mesh=None, batch_axes=("data",),
              fsdp_axes=("data",)):
    """x: (B, S, d) -> (y, aux).  Sharded path uses shard_map over mesh."""
    act = activation(cfg.act)
    B, S, d = x.shape

    if mesh is None:
        xt = x.reshape(B * S, d)
        cap = capacity(B * S, cfg)
        out, aux = _moe_local(params, xt, cfg, cfg.num_experts, 0, cap, act)
        out = out + _shared_partial(params, xt, act)
        return out.reshape(B, S, d), aux

    batch_axes = tuple(batch_axes)
    fsdp_axes = tuple(fsdp_axes)
    model_size = mesh.shape["model"]
    e_local = cfg.num_experts // model_size
    data_size = 1
    for a in batch_axes:
        data_size *= mesh.shape[a]
    tokens_local = (B // data_size) * S
    cap = capacity(tokens_local, cfg)
    two_d = cfg.param_sharding == "fsdp_tp"

    bspec = P(batch_axes if batch_axes else None, None, None)
    expert_spec = P("model", None, fsdp_axes) if two_d else P("model", None, None)
    expert_spec_dn = P("model", fsdp_axes, None) if two_d else P("model", None, None)
    shared_spec = {"shared_w_gate": P(None, "model"),
                   "shared_w_up": P(None, "model"),
                   "shared_w_down": P("model", None)}
    pspecs = {"router": P(None, None), "w_gate": expert_spec,
              "w_up": expert_spec, "w_down": expert_spec_dn}
    for name, sp in shared_spec.items():
        if name in params:
            pspecs[name] = sp

    @partial(jax.shard_map, mesh=mesh, in_specs=(pspecs, bspec),
             out_specs=(bspec, P()), check_vma=False)
    def sharded(prm, xl):
        bl, sl, _ = xl.shape
        xt = xl.reshape(bl * sl, d)
        m_idx = jax.lax.axis_index("model")
        if two_d:  # FSDP: all-gather the d_ff shards for this layer's use
            prm = dict(prm)
            prm["w_gate"] = jax.lax.all_gather(prm["w_gate"], fsdp_axes, axis=2, tiled=True)
            prm["w_up"] = jax.lax.all_gather(prm["w_up"], fsdp_axes, axis=2, tiled=True)
            prm["w_down"] = jax.lax.all_gather(prm["w_down"], fsdp_axes, axis=1, tiled=True)
        out, aux = _moe_local(prm, xt, cfg, e_local, m_idx * e_local, cap, act)
        out = out + _shared_partial(prm, xt, act)
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, ("model",) + tuple(batch_axes))
        return out.reshape(bl, sl, d), aux

    return sharded(params, x)
