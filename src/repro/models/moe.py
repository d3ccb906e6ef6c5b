"""Mixture-of-Experts FFN: dropless routing over the experts a device holds.

Routing (``moe.route``) scores every token against all ``cfg.num_experts``
and keeps ``experts_per_token``:

- ``router_score="softmax"``: the top k of the softmax, gates renormalised
  over the k, and a Switch load-balance aux loss;
- ``router_score="sigmoid"`` (DeepSeek-V3 ``noaux_tc``): the top k of
  ``sigmoid(logits) + score_correction_bias``; the gates are the sigmoid
  scores without the bias, normalised over the k and scaled by
  ``routed_scaling_factor``; no aux loss.  The bias is a fixed buffer
  (``score_correction_bias`` in the layer's parameters at call time, never
  a sampled parameter); one expert group, so group-limited routing does
  nothing.

Gates are always normalised over the k: every configuration here has
``norm_topk_prob``.

Dispatch is dropless (``moe.dispatch``): the (token, expert) pairs are
sorted by expert, the held experts first, and the tokens gathered in that
order into a buffer of ``T * k`` rows, enough for every token to go to the
held experts.  A grouped matmul (``repro.kernels.grouped_matmul``,
``moe.experts``) runs each held expert's SwiGLU over its own rows only; the
rows of experts held elsewhere come back zero.  ``moe.combine`` puts the
rows back in token order and sums each token's k rows under its gates;
``moe.shared`` adds the shared experts.

Expert share: a device holds ``cfg.num_held`` experts from
``cfg.expert_offset``.  The layer routes over all experts and returns the
held experts' part of the result plus the shared experts, the partial that
expert parallelism sums across devices; no code stands in for the experts
held elsewhere.  On a mesh, ``shard_map`` divides the held experts over the
``model`` axis, runs the same local computation on each shard, and sums the
partials (the shared experts' column-sharded partials with them) with one
psum.  The third result is the tokens routed to each held expert.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.grouped_matmul import grouped_matmul
from repro.models.common import activation, dense_init

#: the fixed routing buffer's key in a layer's MoE parameters
BIAS = "score_correction_bias"


def init_moe(key, cfg, dtype) -> dict:
    E, e, d, f = cfg.num_experts, cfg.num_held, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 7)
    params = {
        "router": dense_init(ks[0], (d, E), jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, f), dtype),
        "w_up": dense_init(ks[2], (e, d, f), dtype),
        "w_down": dense_init(ks[3], (e, f, d), dtype),
    }
    if cfg.num_shared_experts > 0:
        fs = f * cfg.num_shared_experts
        params["shared_w_gate"] = dense_init(ks[4], (d, fs), dtype)
        params["shared_w_up"] = dense_init(ks[5], (d, fs), dtype)
        params["shared_w_down"] = dense_init(ks[6], (fs, d), dtype)
    return params


def route(params, xt, cfg):
    """xt (T, d) -> (experts (T, k) int32, gates (T, k) f32, aux loss)."""
    k, E = cfg.experts_per_token, cfg.num_experts
    with jax.named_scope("moe.route"):
        logits = xt.astype(jnp.float32) @ params["router"]  # (T, E)
        if cfg.router_score == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            choice = scores + params[BIAS] if BIAS in params else scores
            _, idx = jax.lax.top_k(choice, k)
            gates = jnp.take_along_axis(scores, idx, axis=1)
            aux = jnp.float32(0.0)
        else:
            probs = jax.nn.softmax(logits, axis=-1)
            gates, idx = jax.lax.top_k(probs, k)
            # Switch-style load-balance aux over the full router output
            frac_tokens = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32),
                                   axis=(0, 1))
            aux = E * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        return idx, gates * cfg.routed_scaling_factor, aux


def _experts_local(params, xt, idx, gates, cfg, e_local: int, e_offset, act):
    """The held experts' part of the routed result, dropless.

    xt (T, d); idx, gates (T, k).  Returns (out (T, d) f32, tokens routed to
    each of the ``e_local`` experts from ``e_offset`` (e_local,) int32).
    """
    T, _ = xt.shape
    k = idx.shape[1]
    with jax.named_scope("moe.dispatch"):
        # held experts first (0 .. e_local-1), then the rest in id order
        local = jnp.mod(idx.reshape(-1) - e_offset, cfg.num_experts)
        order = jnp.argsort(local, stable=True)
        sizes = jnp.bincount(local, length=cfg.num_experts)[:e_local]
        sizes = sizes.astype(jnp.int32)
        rows = xt[order // k]  # (T*k, d) in expert order
    with jax.named_scope("moe.experts"):
        h = act(grouped_matmul(rows, params["w_gate"], sizes)) * \
            grouped_matmul(rows, params["w_up"], sizes)
        y = grouped_matmul(h, params["w_down"], sizes)  # 0 past held rows
    with jax.named_scope("moe.combine"):
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        held = (local < e_local).reshape(T, k)
        y = y[back].reshape(T, k, -1).astype(jnp.float32)
        out = jnp.einsum("tkd,tk->td", y, jnp.where(held, gates, 0.0))
    return out, sizes


def _shared_partial(params, xt, act):
    if "shared_w_gate" not in params:
        return 0.0
    with jax.named_scope("moe.shared"):
        h = act(xt @ params["shared_w_gate"]) * (xt @ params["shared_w_up"])
        return (h @ params["shared_w_down"]).astype(jnp.float32)


def _layer_local(params, xt, cfg, e_local: int, e_offset, act):
    idx, gates, aux = route(params, xt, cfg)
    out, load = _experts_local(params, xt, idx, gates, cfg, e_local,
                               e_offset, act)
    return out + _shared_partial(params, xt, act), aux, load


def apply_moe(params, x, cfg, mesh=None, batch_axes=("data",),
              fsdp_axes=("data",)):
    """x: (B, S, d) -> (y, aux, tokens routed to each held expert).  The
    sharded path uses shard_map over ``mesh``."""
    act = activation(cfg.act)
    B, S, d = x.shape

    if mesh is None:
        out, aux, load = _layer_local(params, x.reshape(B * S, d), cfg,
                                      cfg.num_held, cfg.expert_offset, act)
        return out.astype(x.dtype).reshape(B, S, d), aux, load

    batch_axes = tuple(batch_axes)
    fsdp_axes = tuple(fsdp_axes)
    e_local = cfg.num_held // mesh.shape["model"]
    two_d = cfg.param_sharding == "fsdp_tp"

    bspec = P(batch_axes if batch_axes else None, None, None)
    expert_spec = P("model", None, fsdp_axes) if two_d else P("model", None, None)
    expert_spec_dn = P("model", fsdp_axes, None) if two_d else P("model", None, None)
    shared_spec = {"shared_w_gate": P(None, "model"),
                   "shared_w_up": P(None, "model"),
                   "shared_w_down": P("model", None), BIAS: P(None)}
    pspecs = {"router": P(None, None), "w_gate": expert_spec,
              "w_up": expert_spec, "w_down": expert_spec_dn}
    for name, sp in shared_spec.items():
        if name in params:
            pspecs[name] = sp

    @partial(jax.shard_map, mesh=mesh, in_specs=(pspecs, bspec),
             out_specs=(bspec, P(), P("model")), check_vma=False)
    def sharded(prm, xl):
        bl, sl, _ = xl.shape
        xt = xl.reshape(bl * sl, d)
        m_idx = jax.lax.axis_index("model")
        if two_d:  # FSDP: all-gather the d_ff shards for this layer's use
            prm = dict(prm)
            prm["w_gate"] = jax.lax.all_gather(prm["w_gate"], fsdp_axes, axis=2, tiled=True)
            prm["w_up"] = jax.lax.all_gather(prm["w_up"], fsdp_axes, axis=2, tiled=True)
            prm["w_down"] = jax.lax.all_gather(prm["w_down"], fsdp_axes, axis=1, tiled=True)
        out, aux, load = _layer_local(prm, xt, cfg, e_local,
                                      cfg.expert_offset + m_idx * e_local, act)
        out = jax.lax.psum(out, "model").astype(xl.dtype)
        aux = jax.lax.pmean(aux, ("model",) + tuple(batch_axes))
        if batch_axes:
            load = jax.lax.psum(load, tuple(batch_axes))
        return out.reshape(bl, sl, d), aux, load

    return sharded(params, x)
