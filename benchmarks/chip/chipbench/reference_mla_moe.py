"""The plain reference of a DeepSeek-V3 decoder (latent attention, sigmoid
routing with a fixed correction bias, shared experts, leading dense
layers): forward pass, loss and W-Con SGLD commits in float32
``jax.numpy``, written from the published modeling code
(``modeling_deepseek.py``) and importing nothing of the program.

It reads a configuration file's own keys (HF names: ``kv_lora_rank``,
``qk_nope_head_dim``, ``n_routed_experts`` and the rest) and a parameter
tree in the program's layout, the one the benchmark's weights are made in
(``weights.py``): ``lead`` (the dense layers, a list), then ``stack`` (the
MoE layers on a leading axis), every matrix ``in x out``.  The chip's share
of the layer is the file's: ``n_routed_experts`` experts held from
``deployment["first_expert"]``, routed among ``deployment["router_outputs"]``;
the held experts' part of the result and the shared experts go on to the
next layer, as the program's do.

Arithmetic, the float8 control, the noise stream, the commit and
``change_gap`` are ``reference.py``'s.  Departures from the dense
reference: attention is computed ``QBLOCK`` queries at a time (8k
sequences fit in float32), and each held expert runs densely on every token
with its output counted where the gate chose it (no sort, no grouped
matmul).

Published conventions kept: the rope dims come as interleaved pairs,
viewed as (r/2, 2) and transposed to halves before ``x cos +
rotate_half(x) sin``; the latent's RMSNorm (``kv_a_layernorm``) uses its
module default eps 1e-6; the softmax scale is ``(nope + rope)^-1/2``; the
gate picks the top k of ``sigmoid(logits) + bias`` and weighs by the
unbiased scores, normalised over the k and scaled by
``routed_scaling_factor``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import HIGHEST, Trajectory, _commit, matmul, \
    noise_bits, rms

#: queries per attention block
QBLOCK = 1024
#: eps of the latent's RMSNorm (DeepseekV3RMSNorm's default)
LATENT_EPS = 1e-6


@dataclass(frozen=True)
class MoEConfig:
    d: int
    heads: int
    nope: int
    rope: int
    v: int
    rank: int
    vocab: int
    theta: float
    eps: float
    k: int
    experts: int           # router outputs
    first_expert: int      # the first expert held here
    scale: float
    norm_topk: bool

    @classmethod
    def from_file(cls, conf: dict) -> "MoEConfig":
        dep = conf["deployment"]
        return cls(d=conf["hidden_size"], heads=conf["num_attention_heads"],
                   nope=conf["qk_nope_head_dim"],
                   rope=conf["qk_rope_head_dim"], v=conf["v_head_dim"],
                   rank=conf["kv_lora_rank"], vocab=conf["vocab_size"],
                   theta=float(conf["rope_theta"]),
                   eps=float(conf["rms_norm_eps"]),
                   k=conf["num_experts_per_tok"],
                   experts=dep["router_outputs"],
                   first_expert=dep["first_expert"],
                   scale=float(conf["routed_scaling_factor"]),
                   norm_topk=bool(conf["norm_topk_prob"]))


def rotary(x, theta):
    """DeepseekV3 ``apply_rotary_pos_emb`` on x (B, S, h, r)."""
    B, S, h, r = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    freqs = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([freqs, freqs], -1)
    cos, sin = jnp.cos(emb)[None, :, None], jnp.sin(emb)[None, :, None]
    x = x.reshape(B, S, h, r // 2, 2).swapaxes(-1, -2).reshape(B, S, h, r)
    rot = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * cos + rot * sin


def attention(q, k, v):
    """Causal softmax attention, ``QBLOCK`` queries at a time: q, k
    (B, S, H, dq), v (B, S, H, dv)."""
    B, S, H, dq = q.shape
    nb = -(-S // QBLOCK)
    qb = jnp.pad(q, ((0, 0), (0, nb * QBLOCK - S), (0, 0), (0, 0)))
    qb = qb.reshape(B, nb, QBLOCK, H, dq).swapaxes(0, 1)

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=HIGHEST)
        s = s * dq ** -0.5
        rows = i * QBLOCK + jnp.arange(QBLOCK)
        s = jnp.where(rows[:, None] >= jnp.arange(S)[None, :], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                          precision=HIGHEST)

    o = jax.lax.map(block, (jnp.arange(nb), qb))  # (nb, B, QBLOCK, H, dv)
    return o.swapaxes(0, 1).reshape(B, nb * QBLOCK, H, -1)[:, :S]


def mla(cfg: MoEConfig, prec: str, p, h):
    B, S, _ = h.shape
    H, dn = cfg.heads, cfg.nope
    q = matmul(h, p["wq"], prec).reshape(B, S, H, dn + cfg.rope)
    ckv = matmul(h, p["wkv_a"], prec)
    kv = matmul(rms(ckv[..., :cfg.rank], p["kv_norm"], LATENT_EPS),
                p["wkv_b"], prec).reshape(B, S, H, dn + cfg.v)
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], cfg.theta)], -1)
    k_pe = rotary(ckv[..., None, cfg.rank:], cfg.theta)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (B, S, H, cfg.rope))], -1)
    o = attention(q, k, kv[..., dn:])
    return matmul(o.reshape(B, S, H * cfg.v), p["wo"], prec)


def swiglu(prec: str, h, w_gate, w_up, w_down):
    return matmul(jax.nn.silu(matmul(h, w_gate, prec))
                  * matmul(h, w_up, prec), w_down, prec)


def gates(cfg: MoEConfig, prec: str, x, router, bias):
    """MoEGate, noaux_tc, one group: (T, router outputs) weights, zero
    where an expert was not chosen."""
    scores = jax.nn.sigmoid(matmul(x, router, prec))
    _, idx = jax.lax.top_k(scores + bias[None], cfg.k)
    chosen = jnp.sum(jax.nn.one_hot(idx, cfg.experts), axis=1) > 0
    w = jnp.where(chosen, scores, 0.0)
    if cfg.norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * cfg.scale


def moe(cfg: MoEConfig, prec: str, p, h, bias):
    """The held experts' part of DeepseekV3MoE, plus the shared experts;
    also the tokens routed to each held expert."""
    B, S, d = h.shape
    x = h.reshape(B * S, d)
    w = gates(cfg, prec, x, p["router"], bias)
    held = p["w_gate"].shape[0]
    wh = jax.lax.dynamic_slice_in_dim(w, cfg.first_expert, held, axis=1)

    def expert(y, e):
        out = swiglu(prec, x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        return y + wh[:, e, None] * out, None

    y, _ = jax.lax.scan(jax.checkpoint(expert),
                        jnp.zeros((B * S, d), jnp.float32), jnp.arange(held))
    y = y + swiglu(prec, x, p["shared_w_gate"], p["shared_w_up"],
                   p["shared_w_down"])
    return y.reshape(B, S, d), jnp.sum(wh > 0, axis=0)


def layer(cfg: MoEConfig, prec: str, x, p, bias=None):
    """One pre-norm layer: latent attention, then the dense MLP (``bias``
    None) or the MoE."""
    x = x + mla(cfg, prec, p["attn"], rms(x, p["norm1"], cfg.eps))
    h = rms(x, p["norm2"], cfg.eps)
    if bias is None:
        m = p["mlp"]
        return x + swiglu(prec, h, m["w_gate"], m["w_up"], m["w_down"]), None
    y, load = moe(cfg, prec, p["moe"], h, bias)
    return x + y, load


def hidden(cfg: MoEConfig, prec: str, params, tokens, biases):
    """Final-normed hidden states (B, S, d) in float32, and the tokens
    routed to each held expert of each MoE layer."""
    x = jnp.take(params["embed"]["w"], tokens, axis=0).astype(jnp.float32)
    for p in params["lead"]:
        x, _ = jax.checkpoint(
            lambda x, p: layer(cfg, prec, x, p))(x, p)
    body = jax.checkpoint(lambda x, pb: layer(cfg, prec, x, *pb))
    x, loads = jax.lax.scan(body, x, (params["stack"], biases))
    return rms(x, params["final_norm"], cfg.eps), loads


def make_loss(cfg: MoEConfig, prec: str, biases, block: int = 1024):
    """Mean next-token cross-entropy over a batch (B, S+1), the head and
    its softmax taken ``block`` tokens at a time; aux: the held loads."""

    def loss(params, tokens):
        inp, labels = tokens[:, :-1], tokens[:, 1:]
        h, loads = hidden(cfg, prec, params, inp, biases)
        d = h.shape[-1]
        h, labels = h.reshape(-1, d), labels.reshape(-1)
        n = h.shape[0]
        nb = -(-n // block)
        pad = nb * block - n
        h = jnp.pad(h, ((0, pad), (0, 0))).reshape(nb, block, d)
        lab = jnp.pad(labels, (0, pad)).reshape(nb, block)
        w = params["lm_head"]["w"]

        @jax.checkpoint
        def blk(hb, lb):
            logp = jax.nn.log_softmax(matmul(hb, w, prec), axis=-1)
            return -jnp.take_along_axis(logp, lb[:, None], axis=1)[:, 0]

        nll = jax.lax.map(lambda a: blk(*a), (h, lab)).reshape(-1)[:n]
        return jnp.mean(nll), loads

    return loss


def sgld_commits(cfg: MoEConfig, prec: str, x0, batches, delays, chain_key,
                 gamma: float, sigma: float, biases, *, depth: int = 3,
                 zero_grad: bool = False) -> Trajectory:
    """K commits of W-Con SGLD from ``x0``, as ``reference.sgld_commits``
    with this model's loss (``biases`` (MoE layers, router outputs)).  The
    trajectory also carries ``loads``: each commit's tokens routed to each
    held expert of each MoE layer (K, layers, held)."""
    loss = make_loss(cfg, prec, jnp.asarray(biases, jnp.float32))
    grad = jax.jit(jax.value_and_grad(loss, has_aux=True))
    upcast = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), t))
    bits = noise_bits(chain_key, len(delays))
    g32 = jnp.float32(gamma)
    scale = jnp.sqrt(jnp.float32(2.0 * sigma) * g32)
    ring = {0: jax.device_get(x0)}
    x = x0
    losses, loads, norms = [], [], None
    for k, tau in enumerate(np.asarray(delays)):
        xh = upcast(jax.device_put(ring[k - int(tau)]))
        (val, load), g = grad(xh, jnp.asarray(batches[k]))
        del xh
        losses.append(float(val))
        loads.append(np.asarray(load))
        if norms is None:
            norms = [float(jnp.linalg.norm(leaf.reshape(-1)))
                     for leaf in jax.tree_util.tree_leaves(g)]
        if zero_grad:
            g = jax.tree_util.tree_map(jnp.zeros_like, g)
        x = _commit(x, g, bits[k], g32, scale)
        del g
        ring[k + 1] = jax.device_get(x)
        ring.pop(k + 1 - depth, None)
    out = Trajectory(np.asarray(losses), ring[len(delays)], norms)
    out.loads = np.stack(loads)
    return out
