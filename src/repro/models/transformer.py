"""Model assembly: init, forward (train/prefill), decode (serve), loss.

One ``Model`` class covers all 10 assigned architectures via
``cfg.block_pattern``:

- ``attn_mlp``   dense decoder layer (llama-style; qk-norm / qkv-bias /
                 sliding-window per config)
- ``attn_moe``   MoE decoder layer (dropless, expert-parallel, see moe.py)
- ``hymba_mlp``  parallel attention + SSD heads (Hymba), then MLP
- ``mlstm`` / ``slstm``  xLSTM blocks (no separate MLP)

Homogeneous patterns (len == 1) stack layer parameters on a leading axis and
run under ``lax.scan`` (compile-time O(1) in depth); heterogeneous patterns
(xLSTM) use a python loop.  ``cfg.first_k_dense`` leading ``attn_mlp``
layers (DeepSeek's ``first_k_dense_replace``, MLP width ``cfg.dense_d_ff``)
run before the stack, one by one (``params["lead"]``).  Attention is latent
(MLA, mla.py) where ``cfg.kv_lora_rank`` is set.  Every block is wrapped in
``jax.checkpoint`` for training memory.

Decode state is a dict of stacked-per-layer arrays so it threads through the
same scan.  VLM/audio frontends are embedding stubs + a trainable projector
(the one allowed stub, DESIGN.md §2).
"""

from __future__ import annotations

import math
from dataclasses import replace
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.attention import (
    attention_any,
    decode_attention,
    paged_decode_attention,
)
from repro.models.common import (
    apply_rope,
    dense_init,
    dtype_of,
    embed_init,
    head_rms_norm,
    partition_tree,
    rms_norm,
)
from repro.models.mla import apply_mla, init_mla
from repro.models.mlp import apply_mlp, init_mlp

PyTree = Any

FRONTEND_DIM = 1024  # stub embedding width (ViT/EnCodec feature dim)
MLA_SERVING = ("serving latent attention (MLA) or leading dense layers needs "
               "a latent paged cache, which this model does not have; only "
               "the training/sampling forward runs")


# ===========================================================================
# per-component init
# ===========================================================================
def init_attn(key, cfg, dtype) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, cfg.q_dim), dtype),
        "wk": dense_init(ks[1], (d, cfg.kv_dim), dtype),
        "wv": dense_init(ks[2], (d, cfg.kv_dim), dtype),
        "wo": dense_init(ks[3], (cfg.q_dim, d), dtype,
                         scale=1.0 / math.sqrt(cfg.q_dim * 2 * cfg.num_layers)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dtype)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dtype)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
        p["k_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
    return p


def init_block(key, cfg, block: str, dtype) -> dict:
    ks = jax.random.split(key, 4)
    p: dict = {"norm1": jnp.ones((cfg.d_model,), jnp.float32)}
    if block in ("attn_mlp", "attn_moe", "hymba_mlp"):
        p["attn"] = (init_mla if cfg.kv_lora_rank else init_attn)(
            ks[0], cfg, dtype)
        p["norm2"] = jnp.ones((cfg.d_model,), jnp.float32)
    if block == "hymba_mlp":
        p["ssm"] = ssm_lib.init_ssm(ks[1], cfg, dtype)
    if block in ("attn_mlp", "hymba_mlp"):
        p["mlp"] = init_mlp(ks[2], cfg, dtype)
    if block == "attn_moe":
        p["moe"] = moe_lib.init_moe(ks[2], cfg, dtype)
    if block == "mlstm":
        p["mlstm"] = xlstm_lib.init_mlstm(ks[0], cfg, dtype)
    if block == "slstm":
        p["slstm"] = xlstm_lib.init_slstm(ks[0], cfg, dtype)
    return p


def init_params(key, cfg) -> PyTree:
    dtype = dtype_of(cfg)
    k_embed, k_stack, k_head, k_front = jax.random.split(key, 4)
    params: dict = {"embed": {"w": embed_init(k_embed, (cfg.vocab_size, cfg.d_model), dtype)},
                    "final_norm": jnp.ones((cfg.d_model,), jnp.float32)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": dense_init(k_head, (cfg.d_model, cfg.vocab_size), dtype)}
    if cfg.frontend:
        params["frontend"] = {"proj": dense_init(k_front, (FRONTEND_DIM, cfg.d_model), dtype)}

    if cfg.first_k_dense:
        keys = jax.random.split(jax.random.fold_in(key, 5), cfg.first_k_dense)
        dense = replace(cfg, d_ff=cfg.dense_d_ff)
        params["lead"] = [init_block(k, dense, "attn_mlp", dtype)
                          for k in keys]

    pattern = cfg.block_pattern
    if len(pattern) == 1:
        keys = jax.random.split(k_stack, cfg.num_moe_layers)
        params["stack"] = jax.vmap(
            lambda k: init_block(k, cfg, pattern[0], dtype))(keys)
    else:
        keys = jax.random.split(k_stack, cfg.num_layers)
        params["layers"] = [
            init_block(keys[i], cfg, pattern[i % len(pattern)], dtype)
            for i in range(cfg.num_layers)
        ]
    return params


# ===========================================================================
# block application
# ===========================================================================
def apply_attn(p, x, cfg, positions, *, window, cache=None, cur_pos=None,
               fused=False):
    """cache: dict(k, v, pos) for decode; returns (y, new_kv or kv-for-prefill).

    ``fused=True`` (decode only) routes the cached-attention read plus the
    KV-slot write through the Pallas decode-step kernel instead of the
    ``dynamic_update`` + ``decode_attention`` pair.
    """
    B, S, d = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:  # train / prefill
        o = attention_any(q, k, v, causal=True, window=window)
        new_kv = (k, v)
    else:  # decode: S == 1
        smax = cache["k"].shape[1]
        slot = jnp.mod(cur_pos, smax)
        pos_arr = jax.lax.dynamic_update_index_in_dim(
            cache["pos"], jnp.asarray(cur_pos, cache["pos"].dtype), slot, 0)
        if fused:
            from repro.kernels.ops import fused_decode_step

            valid = (pos_arr >= 0) & (pos_arr <= cur_pos)
            if window is not None:
                valid &= pos_arr > (cur_pos - window)
            o, k_cache, v_cache = fused_decode_step(
                q[:, 0], k[:, 0], v[:, 0], cache["k"], cache["v"],
                valid.astype(jnp.int32), slot)
            o = o[:, None]
        else:
            k_cache = jax.lax.dynamic_update_index_in_dim(cache["k"], k[:, 0],
                                                          slot, 1)
            v_cache = jax.lax.dynamic_update_index_in_dim(cache["v"], v[:, 0],
                                                          slot, 1)
            o = decode_attention(q, k_cache, v_cache, pos_arr, cur_pos,
                                 window=window)
        new_kv = {"k": k_cache, "v": v_cache, "pos": pos_arr}
    y = o.reshape(B, S, cfg.q_dim) @ p["wo"]
    return y, new_kv


def apply_paged_attn(p, x, cfg, pages, tables, positions, *, fused=False):
    """Cached attention over a paged KV pool — one slot per row.

    x: (S, 1, d); pages: dict(k, v) of (n_pages, page_size, KV, hd) pools
    shared by every slot; tables: (S, maxp) int32; positions: (S,) absolute
    position per slot (rope + write + validity).  Returns (y, new pages).
    """
    S, _, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(S, 1, cfg.num_heads, cfg.head_dim)
    k = k.reshape(S, 1, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(S, 1, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions[:, None], cfg.rope_theta)
    k = apply_rope(k, positions[:, None], cfg.rope_theta)
    ps = pages["k"].shape[1]
    if fused:
        from repro.kernels.ops import fused_paged_decode_step

        o, k_pool, v_pool = fused_paged_decode_step(
            q[:, 0], k[:, 0], v[:, 0], pages["k"], pages["v"], tables,
            positions)
        o = o[:, None]
    else:
        widx = (tables[jnp.arange(S), positions // ps] * ps + positions % ps)
        kf = pages["k"].reshape(-1, *pages["k"].shape[2:]).at[widx].set(k[:, 0])
        vf = pages["v"].reshape(-1, *pages["v"].shape[2:]).at[widx].set(v[:, 0])
        o = paged_decode_attention(q, kf, vf, tables, positions, ps)
        k_pool = kf.reshape(pages["k"].shape)
        v_pool = vf.reshape(pages["v"].shape)
    y = o.reshape(S, 1, cfg.q_dim) @ p["wo"]
    return y, {"k": k_pool, "v": v_pool}


def apply_paged_block(p, x, cfg, block: str, pages, tables, positions, *,
                      mesh=None, batch_axes=("data",), fsdp_axes=("data",),
                      fused=False):
    """One decode step of an attention block against the paged pool — the
    same residual/norm/MLP ops as :func:`apply_block`'s decode path with
    :func:`apply_paged_attn` in place of the ring-cache attention.  Returns
    (x, new pages)."""
    if block not in ("attn_mlp", "attn_moe"):
        raise ValueError(f"paged decode needs an attention block, got {block!r}")
    rs = cfg.residual_scale
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    attn_out, new_pages = apply_paged_attn(
        p["attn"], h, cfg, pages, tables, positions, fused=fused)
    x = x + rs * attn_out
    h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
    if block == "attn_moe":
        ff, _, _ = moe_lib.apply_moe(p["moe"], h2, cfg, mesh=mesh,
                                     batch_axes=batch_axes,
                                     fsdp_axes=fsdp_axes)
    else:
        ff = apply_mlp(p["mlp"], h2, cfg)
    x = x + rs * ff
    return x, new_pages


def apply_block(p, x, cfg, block: str, positions, *, mesh=None, batch_axes=("data",),
                fsdp_axes=("data",), cache=None, cur_pos=None, fused=False):
    """Returns (x, aux_loss, new_cache, tokens routed to each held expert:
    (num_held,) int32 for an MoE block, else None)."""
    rs = cfg.residual_scale
    aux = jnp.float32(0.0)
    load = None
    new_cache: dict = {}
    window = cfg.sliding_window

    if block in ("attn_mlp", "attn_moe", "hymba_mlp"):
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        if cfg.kv_lora_rank:
            if cache is not None:
                raise NotImplementedError(MLA_SERVING)
            attn_out, kv = apply_mla(p["attn"], h, cfg, positions), None
        else:
            attn_out, kv = apply_attn(
                p["attn"], h, cfg, positions, window=window,
                cache=None if cache is None else cache["attn"],
                cur_pos=cur_pos, fused=fused)
        if block == "hymba_mlp":
            if cache is None:
                ssm_out = ssm_lib.apply_ssm(p["ssm"], h, cfg)
            else:
                st = ssm_lib.SSMState(h=cache["ssm_h"], conv=cache["ssm_conv"])
                ssm_out, new_st = ssm_lib.apply_ssm(p["ssm"], h, cfg, state=st)
                new_cache["ssm_h"], new_cache["ssm_conv"] = new_st.h, new_st.conv
            mix = 0.5 * (attn_out + ssm_out)
        else:
            mix = attn_out
        if cache is not None:
            new_cache["attn"] = kv
        x = x + rs * mix
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        if block == "attn_moe":
            ff, aux, load = moe_lib.apply_moe(p["moe"], h2, cfg, mesh=mesh,
                                              batch_axes=batch_axes,
                                              fsdp_axes=fsdp_axes)
        else:
            ff = apply_mlp(p["mlp"], h2, cfg)
        x = x + rs * ff
        return x, aux, (new_cache if cache is not None else kv), load

    if block == "mlstm":
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        if cache is None:
            out = xlstm_lib.apply_mlstm(p["mlstm"], h, cfg)
        else:
            st = xlstm_lib.MLSTMState(c=cache["mlstm_c"], n=cache["mlstm_n"],
                                      m=cache["mlstm_m"])
            out, new_st = xlstm_lib.apply_mlstm(p["mlstm"], h, cfg, state=st)
            new_cache = {"mlstm_c": new_st.c, "mlstm_n": new_st.n,
                         "mlstm_m": new_st.m}
        return x + rs * out, aux, new_cache, load

    if block == "slstm":
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        if cache is None:
            out = xlstm_lib.apply_slstm(p["slstm"], h, cfg)
        else:
            st = xlstm_lib.SLSTMState(c=cache["slstm_c"], n=cache["slstm_n"],
                                      m=cache["slstm_m"], h=cache["slstm_h"])
            out, new_st = xlstm_lib.apply_slstm(p["slstm"], h, cfg, state=st)
            new_cache = {"slstm_c": new_st.c, "slstm_n": new_st.n,
                         "slstm_m": new_st.m, "slstm_h": new_st.h}
        return x + rs * out, aux, new_cache, load

    raise ValueError(f"unknown block {block!r}")


# ===========================================================================
# the Model
# ===========================================================================
class Model:
    """Config-driven decoder.  Methods are pure; jit at the call site."""

    def __init__(self, cfg, mesh=None, batch_axes=("data",),
                 fsdp_axes=("data",), remat: bool = True,
                 decode_fused: bool = False):
        self.cfg = cfg
        self.mesh = mesh
        self.batch_axes = tuple(batch_axes)
        self.fsdp_axes = tuple(fsdp_axes)
        self.remat = remat
        # opt-in Pallas fused decode step (cached-attention read + KV slot
        # write in one kernel); the unfused path is the parity reference
        self.decode_fused = decode_fused

    # -- embedding ------------------------------------------------------------
    def embed(self, params, batch) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (x (B,S,d), positions (B,S) or (S,))."""
        cfg = self.cfg
        parts = []
        if cfg.frontend:
            fe = batch["frontend"]  # (B, N, FRONTEND_DIM) stub embeddings
            parts.append((fe @ params["frontend"]["proj"]).astype(dtype_of(cfg)))
        if "tokens" in batch:
            tok = batch["tokens"]
            parts.append(jnp.take(params["embed"]["w"], tok, axis=0))
        x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
        positions = jnp.arange(x.shape[1])
        return x, positions

    def unembed(self, params, x) -> jnp.ndarray:
        cfg = self.cfg
        w = (params["embed"]["w"].T if cfg.tie_embeddings
             else params["lm_head"]["w"])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x @ w

    def stack(self, params):
        """The scanned layers' parameters, with the fixed routing buffer
        (``cfg.score_correction_bias``, zeros where none is given) put
        into each sigmoid-routed MoE layer: a constant of the program, not
        a parameter."""
        cfg = self.cfg
        stack = params["stack"]
        if cfg.router_score != "sigmoid" or "moe" not in stack:
            return stack
        bias = (jnp.asarray(cfg.score_correction_bias, jnp.float32)
                if cfg.score_correction_bias else
                jnp.zeros((cfg.num_moe_layers, cfg.num_experts), jnp.float32))
        return dict(stack, moe=dict(stack["moe"], **{moe_lib.BIAS: bias}))

    # -- forward over layers ----------------------------------------------------
    def forward(self, params, batch, want_kv: bool = False):
        """Train/prefill forward. Returns (logits, aux, kv-stack or None)."""
        return self.forward_loads(params, batch, want_kv)[:3]

    def forward_loads(self, params, batch, want_kv: bool = False):
        """:meth:`forward` and, for MoE stacks, the tokens routed to each
        held expert of each MoE layer ((moe layers, num_held) int32, else
        None)."""
        cfg = self.cfg
        x, positions = self.embed(params, batch)

        def block_fn(p, x, block):
            return apply_block(p, x, cfg, block, positions, mesh=self.mesh,
                               batch_axes=self.batch_axes,
                               fsdp_axes=self.fsdp_axes)

        if self.remat:
            block_fn = jax.checkpoint(block_fn, static_argnums=(2,),
                                      policy=jax.checkpoint_policies.nothing_saveable)

        aux_total = jnp.float32(0.0)
        kvs = loads = None
        for layer_p in params.get("lead", ()):
            x, a, _, _ = block_fn(layer_p, x, "attn_mlp")
            aux_total = aux_total + a
        if "stack" in params:
            block = cfg.block_pattern[0]

            def scan_body(carry, layer_p):
                x, aux = carry
                x, a, kv, load = block_fn(layer_p, x, block)
                return (x, aux + a), (kv if want_kv else None, load)

            (x, aux_total), (kvs, loads) = jax.lax.scan(
                scan_body, (x, aux_total), self.stack(params))
        else:
            kvs = []
            for i, layer_p in enumerate(params["layers"]):
                block = cfg.block_pattern[i % len(cfg.block_pattern)]
                x, a, kv, _ = block_fn(layer_p, x, block)
                aux_total = aux_total + a
                kvs.append(kv if want_kv else None)
        logits = self.unembed(params, x)
        return logits, aux_total / cfg.num_layers, kvs, loads

    # -- decode -------------------------------------------------------------------
    def init_cache(self, batch_size: int, max_seq: int, prefill_len: int = 0):
        """Decode cache, stacked per layer (scan-compatible)."""
        cfg = self.cfg
        self._refuse_latent("init_cache")
        dtype = dtype_of(cfg)
        L = cfg.num_layers
        window = cfg.sliding_window
        smax = min(max_seq, window) if window else max_seq

        def attn_entry():
            pos = jnp.where(jnp.arange(smax) < prefill_len,
                            jnp.arange(smax), -1).astype(jnp.int32)
            return {
                "k": jnp.zeros((batch_size, smax, cfg.num_kv_heads, cfg.head_dim), dtype),
                "v": jnp.zeros((batch_size, smax, cfg.num_kv_heads, cfg.head_dim), dtype),
                "pos": pos,
            }

        def entry_for(block):
            e: dict = {}
            if block in ("attn_mlp", "attn_moe", "hymba_mlp"):
                e["attn"] = attn_entry()
            if block == "hymba_mlp":
                st = ssm_lib.init_ssm_state(cfg, batch_size, dtype)
                e["ssm_h"], e["ssm_conv"] = st.h, st.conv
            if block == "mlstm":
                st = xlstm_lib.init_mlstm_state(cfg, batch_size)
                e.update(mlstm_c=st.c, mlstm_n=st.n, mlstm_m=st.m)
            if block == "slstm":
                st = xlstm_lib.init_slstm_state(cfg, batch_size)
                e.update(slstm_c=st.c, slstm_n=st.n, slstm_m=st.m, slstm_h=st.h)
            return e

        if len(cfg.block_pattern) == 1:
            one = entry_for(cfg.block_pattern[0])
            return jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (L,) + a.shape).copy(), one)
        return [entry_for(cfg.block_pattern[i % len(cfg.block_pattern)])
                for i in range(L)]

    def _refuse_latent(self, what: str):
        if self.cfg.kv_lora_rank or self.cfg.first_k_dense:
            raise NotImplementedError(f"{what}: {MLA_SERVING}")

    def _require_stacked_attention(self, what: str):
        cfg = self.cfg
        self._refuse_latent(what)
        if len(cfg.block_pattern) != 1 or cfg.block_pattern[0] not in (
                "attn_mlp", "attn_moe"):
            raise ValueError(
                f"{what} needs a homogeneous attention stack "
                f"(block_pattern ('attn_mlp',) or ('attn_moe',)), got "
                f"{cfg.block_pattern}; SSM/xLSTM states have no prefill-"
                "fillable KV cache")
        if cfg.frontend:
            raise ValueError(f"{what} serves token prompts only "
                             f"(frontend={cfg.frontend!r})")

    def init_cache_bank(self, num_chains: int, batch_size: int, max_seq: int):
        """Chain-stacked decode cache: :meth:`init_cache` with every leaf
        gaining a leading ``(num_chains,)`` axis — the per-chain KV-cache
        bank a :class:`~repro.cluster.decode.DecodeEngine` allocates once
        per bucket rung and donates across serve steps."""
        from repro.utils import tree_broadcast_leading

        self._require_stacked_attention("init_cache_bank")
        return tree_broadcast_leading(self.init_cache(batch_size, max_seq),
                                      num_chains)

    def _require_paged(self, what: str):
        self._require_stacked_attention(what)
        if self.cfg.sliding_window:
            raise ValueError(
                f"{what} serves full attention only: a sliding window would "
                "need per-slot ring pages (the contiguous decode cache "
                "already implements windowed rings)")

    def init_paged_bank(self, num_chains: int, num_pages: int,
                        page_size: int):
        """Paged decode-cache bank: one shared block pool per chain.

        Returns ``{"k", "v"}`` of shape ``(num_chains, num_layers,
        num_pages, page_size, num_kv_heads, head_dim)`` — unlike
        :meth:`init_cache_bank` there is no per-sequence ring; every serving
        slot maps its logical pages into the shared pool through a per-slot
        page table, so mixed-length sequences share HBM without per-request
        reallocation.  Physical page 0 is reserved by the scheduler as the
        garbage page inactive slots write into.  The bank is donated across
        steps by :class:`~repro.cluster.paged.PagedDecodeEngine`.
        """
        self._require_paged("init_paged_bank")
        cfg = self.cfg
        shape = (num_chains, cfg.num_layers, num_pages, page_size,
                 cfg.num_kv_heads, cfg.head_dim)
        dtype = dtype_of(cfg)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def paged_prefill(self, params, tokens, pages, table, prompt_len):
        """Prefill one prompt into its slot's pages.

        ``tokens`` is a bucket-padded ``(1, T_pad)`` prompt with true length
        ``prompt_len`` (traced scalar); ``pages`` is the single-chain pool
        ``{"k", "v"}: (L, n_pages, page_size, KV, hd)``; ``table`` is this
        slot's ``(maxp,)`` page table.  The prompt's per-layer KV scatters
        into logical positions ``[0, T_pad)`` of the slot's pages (pad
        positions carry garbage but stay masked by the positional validity
        until overwritten).  Returns ``(logits at prompt_len - 1 (1, V),
        pages)``.  Single-chain; the engine vmaps it over the bank.
        """
        self._require_paged("paged_prefill")
        T = tokens.shape[1]
        L, _, ps = pages["k"].shape[:3]
        if T > table.shape[0] * ps:
            raise ValueError(
                f"padded prompt length {T} exceeds the slot's "
                f"{table.shape[0]} x {ps} paged capacity (raise max_seq, or "
                "loosen the prompt bucket ladder)")
        logits, _, (k, v) = self.forward(params, {"tokens": tokens},
                                         want_kv=True)  # (L, 1, T, KV, hd)
        last = jax.lax.dynamic_index_in_dim(logits, prompt_len - 1, axis=1,
                                            keepdims=False)  # (1, V)
        r = jnp.arange(T)
        idx = table[r // ps] * ps + r % ps  # logical -> flat physical rows
        kf = pages["k"].reshape(L, -1, *pages["k"].shape[3:])
        vf = pages["v"].reshape(L, -1, *pages["v"].shape[3:])
        return last, {
            "k": kf.at[:, idx].set(k[:, 0]).reshape(pages["k"].shape),
            "v": vf.at[:, idx].set(v[:, 0]).reshape(pages["v"].shape),
        }

    def paged_step(self, params, pages, tables, tokens, positions):
        """One decode step over the serving slots of a paged pool.

        tokens: (S, 1) int32 — the last token of each slot; tables:
        (S, maxp) int32; positions: (S,) int32 absolute position each
        slot's token is written at (the scheduler clamps inactive slots to
        0 and points their table rows at the garbage page).  Returns
        (logits (S, 1, V), new pages).  Single-chain; vmapped over the bank.
        """
        self._require_paged("paged_step")
        cfg = self.cfg
        x = jnp.take(params["embed"]["w"], tokens, axis=0)  # (S, 1, d)
        block = cfg.block_pattern[0]

        def scan_body(x, inp):
            layer_p, pg = inp
            x, new_pg = apply_paged_block(
                layer_p, x, cfg, block, pg, tables, positions,
                mesh=self.mesh, batch_axes=self.batch_axes,
                fsdp_axes=self.fsdp_axes, fused=self.decode_fused)
            return x, new_pg

        x, new_pages = jax.lax.scan(scan_body, x, (self.stack(params), pages))
        logits = self.unembed(params, x)
        return logits, new_pages

    def prefill_cache(self, params, tokens, cache, prompt_len):
        """Padded-prompt prefill *into* a persistent decode cache.

        ``tokens`` is a bucket-padded prompt batch ``(B, T_pad)`` whose real
        length is the traced scalar ``prompt_len`` (<= T_pad); right-padding
        never leaks into real positions because attention is causal.  The
        prompt's per-layer KV lands in cache slots ``[0, T_pad)`` and slots
        at/after ``prompt_len`` are marked empty (pos = -1), so the pad
        entries stay masked until the decode loop overwrites them in ring
        order.  Returns ``(logits at position prompt_len - 1 (B, V), cache)``.

        Single-chain; a chain bank vmaps this together with
        :meth:`serve_step`.
        """
        self._require_stacked_attention("prefill_cache")
        T = tokens.shape[1]
        smax = cache["attn"]["k"].shape[2]  # (L, B, smax, KV, hd)
        if T > smax:
            raise ValueError(
                f"padded prompt length {T} exceeds the cache's {smax} slots "
                "(raise max_seq, or loosen the prompt bucket ladder)")
        logits, _, (k, v) = self.forward(params, {"tokens": tokens},
                                         want_kv=True)
        last = jax.lax.dynamic_index_in_dim(logits, prompt_len - 1, axis=1,
                                            keepdims=False)  # (B, V)
        L = cache["attn"]["k"].shape[0]
        pos = jnp.where(jnp.arange(smax) < prompt_len, jnp.arange(smax),
                        -1).astype(jnp.int32)
        return last, {"attn": {
            "k": cache["attn"]["k"].at[:, :, :T].set(k),
            "v": cache["attn"]["v"].at[:, :, :T].set(v),
            "pos": jnp.broadcast_to(pos[None], (L, smax)),
        }}

    def serve_step(self, params, cache, tokens, cur_pos):
        """One decode step. tokens: (B, 1) int32; cur_pos: scalar int32.

        Returns (logits (B, 1, V), new_cache).
        """
        cfg = self.cfg
        x = jnp.take(params["embed"]["w"], tokens, axis=0)  # (B, 1, d)
        positions = jnp.asarray(cur_pos)[None]

        def block_fn(p, x, block, c):
            return apply_block(p, x, cfg, block, positions, mesh=self.mesh,
                               batch_axes=self.batch_axes,
                               fsdp_axes=self.fsdp_axes, cache=c,
                               cur_pos=cur_pos, fused=self.decode_fused)

        if "stack" in params:
            block = cfg.block_pattern[0]

            def scan_body(x, inp):
                layer_p, c = inp
                x, _, new_c, _ = block_fn(layer_p, x, block, c)
                return x, new_c

            x, new_cache = jax.lax.scan(scan_body, x,
                                        (self.stack(params), cache))
        else:
            new_cache = []
            for i, layer_p in enumerate(params["layers"]):
                block = cfg.block_pattern[i % len(cfg.block_pattern)]
                x, _, c, _ = block_fn(layer_p, x, block, cache[i])
                new_cache.append(c)
        logits = self.unembed(params, x)
        return logits, new_cache

    def prefill(self, params, batch):
        """Full-prompt forward; returns (last-token logits, attn cache).

        For attention architectures the per-layer (k, v) from the forward pass
        become the decode cache (trimmed to the sliding window if set).  For
        SSM/hybrid/xLSTM blocks the recurrent state is rebuilt by the decode
        path itself (examples use ``init_cache`` + replay).
        """
        cfg = self.cfg
        self._refuse_latent("prefill")
        logits, _, kvs = self.forward(params, batch, want_kv=True)
        window = cfg.sliding_window
        if "stack" in params and cfg.block_pattern[0] in ("attn_mlp", "attn_moe"):
            k, v = kvs  # (L, B, S, KV, hd) each
            S = k.shape[2]
            if window and S > window:
                k, v = k[:, :, -window:], v[:, :, -window:]
                pos = jnp.arange(S - window, S, dtype=jnp.int32)
            else:
                pos = jnp.arange(S, dtype=jnp.int32)
            return logits[:, -1:], {"attn": {"k": k, "v": v, "pos": pos}}
        return logits[:, -1:], None


# ===========================================================================
# loss
# ===========================================================================
def loss_fn(model: Model, params, batch) -> tuple[jnp.ndarray, dict]:
    """Next-token cross-entropy (+ MoE aux).  batch carries 'tokens' (B, S+1)
    and optionally 'frontend'; loss is computed on token positions only.
    The metrics of an MoE stack carry ``expert_tokens``: the tokens routed
    to each held expert of each MoE layer ((moe layers, num_held) int32)."""
    cfg = model.cfg
    tokens = batch["tokens"]
    inp = dict(batch)
    inp["tokens"] = tokens[:, :-1]
    logits, aux, _, loads = model.forward_loads(params, inp)
    labels = tokens[:, 1:]
    n_text = labels.shape[1]
    logits_text = logits[:, -n_text:]  # skip frontend positions
    logp = jax.nn.log_softmax(logits_text.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    ce = -jnp.mean(ll)
    total = ce + cfg.router_aux_coef * aux
    metrics = {"ce": ce, "aux": aux}
    if loads is not None:
        metrics["expert_tokens"] = loads
    return total, metrics


partition_tree = partition_tree  # re-export for repro.models namespace
