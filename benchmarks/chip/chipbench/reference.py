"""The plain reference: a decoder's forward pass, loss and SGLD commits in
float32 ``jax.numpy``, written from the published architecture and the
paper's update, importing nothing of the program.

It reads a configuration file's own keys (HF names) and a parameter tree
in the layout the benchmark's weights are made in (``weights.py``).  Every
matmul runs at ``highest`` precision in float32, unless ``prec="fp8"``: the
control, in which each matmul's operands are first rounded to float8 e4m3
with one scale per weight tensor and one per activation row, the step below
the bfloat16 the configurations state.

The SGLD commit is ``x <- x - gamma * g(x_hat) + sqrt(2 sigma gamma) xi``
rounded once to the parameter's dtype, with ``x_hat`` the iterate the
schedule's staleness names (W-Con).  The Gaussian ``xi`` is the sampler's
stated noise stream: per leaf ``i`` (flattening order) the seed words
``(k0 ^ (0x85EBCA6B (i+1)), k1 + i)`` of the commit's noise key, the leaf's
row-major element index as the threefry2x32 counter pair ``(c, c ^
0x9E3779B9)``, and Box-Muller on the top 24 bits of each word.  JAX's own
``threefry2x32`` primitive computes the hash.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


@dataclass(frozen=True)
class RefConfig:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    theta: float
    eps: float
    tied: bool
    qk_norm: bool

    @classmethod
    def from_file(cls, conf: dict) -> "RefConfig":
        return cls(layers=conf["num_hidden_layers"], d=conf["hidden_size"],
                   heads=conf["num_attention_heads"],
                   kv_heads=conf["num_key_value_heads"],
                   head_dim=conf["head_dim"], vocab=conf["vocab_size"],
                   theta=float(conf["rope_theta"]),
                   eps=float(conf["rms_norm_eps"]),
                   tied=bool(conf["tie_word_embeddings"]),
                   qk_norm=bool(conf.get("qk_norm", False)))


# ---------------------------------------------------------------------------
# arithmetic in the reference's precision
# ---------------------------------------------------------------------------
def _fp8(x, axis):
    """Round to float8 e4m3 with a scale over ``axis`` (None: the tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def matmul(x, w, prec: str):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if prec == "fp8":
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rope(x, theta):
    """Rotary embedding, halves rotated (x: (B, S, H, hd))."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def layer(cfg: RefConfig, prec: str, x, p):
    """One pre-norm decoder layer: causal GQA attention and a SwiGLU MLP,
    each added back to the residual stream."""
    B, S, _ = x.shape
    a = p["attn"]
    h = rms(x, p["norm1"], cfg.eps)
    q = matmul(h, a["wq"], prec).reshape(B, S, cfg.heads, cfg.head_dim)
    k = matmul(h, a["wk"], prec).reshape(B, S, cfg.kv_heads, cfg.head_dim)
    v = matmul(h, a["wv"], prec).reshape(B, S, cfg.kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms(q, a["q_norm"], cfg.eps)
        k = rms(k, a["k_norm"], cfg.eps)
    q, k = rope(q, cfg.theta), rope(k, cfg.theta)
    g = cfg.heads // cfg.kv_heads
    q = q.reshape(B, S, cfg.kv_heads, g, cfg.head_dim)
    s = jnp.einsum("bqngh,bknh->bngqk", q, k, precision=HIGHEST)
    s = s / math.sqrt(cfg.head_dim)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bngqk,bknh->bqngh", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST).reshape(B, S, cfg.heads * cfg.head_dim)
    x = x + matmul(o, a["wo"], prec)
    m = p["mlp"]
    h = rms(x, p["norm2"], cfg.eps)
    ff = jax.nn.silu(matmul(h, m["w_gate"], prec)) * matmul(h, m["w_up"], prec)
    return x + matmul(ff, m["w_down"], prec)


def hidden(cfg: RefConfig, prec: str, params, tokens):
    """Final-normed hidden states (B, S, d) in float32."""
    x = jnp.take(params["embed"]["w"], tokens, axis=0).astype(jnp.float32)
    body = jax.checkpoint(lambda x, p: (layer(cfg, prec, x, p), None))
    x, _ = jax.lax.scan(body, x, params["stack"])
    return rms(x, params["final_norm"], cfg.eps)


def head_weight(cfg: RefConfig, params):
    return params["embed"]["w"].T if cfg.tied else params["lm_head"]["w"]


# ---------------------------------------------------------------------------
# sampling: the loss and the SGLD commits
# ---------------------------------------------------------------------------
def make_loss(cfg: RefConfig, prec: str, block: int = 1024):
    """Mean next-token cross-entropy over a batch (B, S+1), the head and
    its softmax taken ``block`` tokens at a time to bound memory."""

    def loss(params, tokens):
        inp, labels = tokens[:, :-1], tokens[:, 1:]
        h = hidden(cfg, prec, params, inp)
        d = h.shape[-1]
        h, labels = h.reshape(-1, d), labels.reshape(-1)
        n = h.shape[0]
        nb = -(-n // block)
        pad = nb * block - n
        h = jnp.pad(h, ((0, pad), (0, 0))).reshape(nb, block, d)
        lab = jnp.pad(labels, (0, pad)).reshape(nb, block)
        w = head_weight(cfg, params)

        @jax.checkpoint
        def blk(hb, lb):
            logp = jax.nn.log_softmax(matmul(hb, w, prec), axis=-1)
            return -jnp.take_along_axis(logp, lb[:, None], axis=1)[:, 0]

        nll = jax.lax.map(lambda a: blk(*a), (h, lab)).reshape(-1)[:n]
        return jnp.mean(nll)

    return loss


def leaf_noise(bits, index: int, shape) -> jnp.ndarray:
    """The sampler's standard normals for leaf ``index`` under the noise
    key words ``bits`` ((2,) uint32)."""
    from jax.extend.random import threefry2x32_p

    k0 = bits[0] ^ jnp.uint32((0x85EBCA6B * (index + 1)) & 0xFFFFFFFF)
    k1 = bits[1] + jnp.uint32(index)
    n = int(np.prod(shape)) if shape else 1
    c = jnp.arange(n, dtype=jnp.uint32)
    b0, b1 = threefry2x32_p.bind(jnp.broadcast_to(k0, c.shape),
                                 jnp.broadcast_to(k1, c.shape),
                                 c, c ^ jnp.uint32(0x9E3779B9))

    def uniform(b):
        u = jax.lax.shift_right_logical(b, jnp.uint32(8)).astype(jnp.float32)
        return u * jnp.float32(2 ** -24) + jnp.float32(2 ** -25)

    u1, u2 = uniform(b0), uniform(b1)
    z = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(
        jnp.float32(2.0 * 3.14159265358979) * u2)
    return z.reshape(shape)


@partial(jax.jit, static_argnums=(3,), donate_argnums=(0,))
def _commit_leaf(p, g, bits, index, gamma, scale):
    return (p.astype(jnp.float32) - gamma * g.astype(jnp.float32)
            + scale * leaf_noise(bits, index, p.shape)).astype(p.dtype)


def _commit(x, g, bits, gamma, scale):
    """One commit, leaf by leaf, so that one leaf's noise is in memory at
    a time."""
    leaves, tree = jax.tree_util.tree_flatten(x)
    grads = jax.tree_util.tree_leaves(g)
    out = [_commit_leaf(p, gr, bits, i, gamma, scale)
           for i, (p, gr) in enumerate(zip(leaves, grads))]
    return jax.tree_util.tree_unflatten(tree, out)


def noise_bits(chain_key, steps: int) -> list:
    """The noise key words of each commit: the carried chain key is split
    into (next key, noise key, delay key) once per commit."""
    out, key = [], chain_key
    for _ in range(steps):
        key, k_noise, _ = jax.random.split(key, 3)
        out.append(jnp.asarray(jax.random.key_data(k_noise)
                               if jnp.issubdtype(k_noise.dtype,
                                                 jax.dtypes.prng_key)
                               else k_noise, jnp.uint32))
    return out


@dataclass
class Trajectory:
    losses: np.ndarray           # (K,) loss of each commit at its read point
    final: dict                  # host tree: the iterate after K commits
    grad_norms: list             # per leaf: norm of the first gradient


def sgld_commits(cfg: RefConfig, prec: str, x0, batches, delays,
                 chain_key, gamma: float, sigma: float, *,
                 depth: int = 3, zero_grad: bool = False) -> Trajectory:
    """K commits of W-Con SGLD from ``x0`` (a device tree).  ``batches``
    (K, B, S+1) host tokens, ``delays`` (K,) the staleness of each commit.
    The last ``depth`` iterates stay on the host; two float32 copies of the
    parameters (the read point and its gradient) are on the device.
    ``zero_grad`` commits the noise alone (a planted fault)."""
    loss = make_loss(cfg, prec)
    grad = jax.jit(jax.value_and_grad(loss))
    upcast = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), t))
    bits = noise_bits(chain_key, len(delays))
    g32 = jnp.float32(gamma)
    scale = jnp.sqrt(jnp.float32(2.0 * sigma) * g32)
    ring = {0: jax.device_get(x0)}
    x = x0
    losses, norms = [], None
    for k, tau in enumerate(np.asarray(delays)):
        read = ring[k - int(tau)]
        xh = upcast(jax.device_put(read))
        val, g = grad(xh, jnp.asarray(batches[k]))
        del xh
        losses.append(float(val))
        if norms is None:
            norms = [float(jnp.linalg.norm(leaf.reshape(-1)))
                     for leaf in jax.tree_util.tree_leaves(g)]
        if zero_grad:
            g = jax.tree_util.tree_map(jnp.zeros_like, g)
        x = _commit(x, g, bits[k], g32, scale)
        del g
        ring[k + 1] = jax.device_get(x)
        ring.pop(k + 1 - depth, None)
    return Trajectory(np.asarray(losses), ring[len(delays)], norms)


def change_gap(x0, prog, ref, grad_norms) -> tuple:
    """Worst leaf gap between the program's and the reference's norm of the
    parameters' change, over the larger of that leaf's and the median
    leaf's reference change.  Leaves whose reference gradient is below a
    thousandth of the median leaf's are left out (their count returned)."""
    def change(a, b):
        return float(np.linalg.norm(np.asarray(a, np.float32).reshape(-1)
                                    - np.asarray(b, np.float32).reshape(-1)))

    l0 = jax.tree_util.tree_leaves(x0)
    lp = jax.tree_util.tree_leaves(prog)
    lr = jax.tree_util.tree_leaves(ref)
    dp = [change(p, o) for p, o in zip(lp, l0)]
    dr = [change(r, o) for r, o in zip(lr, l0)]
    gmed = float(np.median(grad_norms))
    keep = [i for i, g in enumerate(grad_norms) if g >= 1e-3 * gmed]
    med = float(np.median([dr[i] for i in keep]))
    worst = max(abs(dp[i] - dr[i]) / max(dr[i], med, 1e-30) for i in keep)
    return worst, len(grad_norms) - len(keep)
