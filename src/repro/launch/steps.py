"""Step builders + sharding specs for the dry-run and the real launcher.

For each (arch, shape, mesh) this module constructs:
  - the jit-able step function (train / prefill / decode),
  - ShapeDtypeStruct input stand-ins with NamedShardings attached,
  - out_shardings trees,
so dryrun.py only has to ``.lower().compile()``.

SGLD modes exposed here:
  - ``sync``      paper-faithful Sync baseline (gradient all-reduce on the
                  critical path) — the §Perf *baseline*.
  - ``pipeline``  paper's tau=1 W-Con adapted to TPU: apply last step's
                  all-reduced gradient, overlap this step's all-reduce —
                  the beyond-paper optimized mode.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ArchConfig, ShapeConfig
from repro.samplers.transforms import noise_like as langevin_noise
from repro.samplers.transforms import sgld_apply as apply_update
from repro.data import make_specs
from repro.launch.mesh import batch_axes_for, fsdp_axes_for
from repro.models.common import partition_tree
from repro.models.transformer import Model, init_params
from repro.train.loop import make_grad_fn

PyTree = Any

LONG_CONTEXT_WINDOW = 8192  # sliding window applied to attention archs @500k


def adapt_config(cfg: ArchConfig, shape: ShapeConfig,
                 opts: tuple = ()) -> ArchConfig:
    """Shape-dependent config tweaks (DESIGN.md §4) + §Perf opt switches.

    opts: subset of {"attn_shard", "fsdp", "unroll", "padvocab"}."""
    if shape.name == "long_500k" and cfg.family not in ("ssm",) \
            and cfg.sliding_window is None:
        cfg = replace(cfg, sliding_window=LONG_CONTEXT_WINDOW)
    if "attn_shard" in opts:
        cfg = replace(cfg, opt_attn_head_shard=True)
    if "fsdp" in opts:
        assert cfg.num_experts == 0, "fsdp opt is for dense archs"
        cfg = replace(cfg, param_sharding="fsdp_full",
                      opt_attn_head_shard=False)
    if "unroll" in opts:
        cfg = replace(cfg, opt_unroll_layers=True)
    if "padvocab" in opts:
        # standard practice: pad vocab to a shardable multiple so the embed
        # table and the (B,S,V) logits shard over the model axis
        v = -(-cfg.vocab_size // 256) * 256
        cfg = replace(cfg, vocab_size=v)
    return cfg


def build_model(cfg: ArchConfig, shape: ShapeConfig, mesh, opts: tuple = ()):
    cfg = adapt_config(cfg, shape, opts)
    baxes = batch_axes_for(mesh, shape.global_batch) if mesh is not None else ()
    if cfg.param_sharding == "fsdp_full" and mesh is not None:
        allax = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
        if shape.global_batch % mesh.size == 0:
            baxes = allax  # batch over every axis: zero TP collectives
    faxes = fsdp_axes_for(mesh) if mesh is not None else ("data",)
    model = Model(cfg, mesh=mesh, batch_axes=baxes or (), fsdp_axes=faxes)
    return model, cfg, baxes, faxes


def named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def sanitize_spec(spec: P, shape: tuple, mesh) -> P:
    """Drop sharding on dims the mesh axes don't divide (explicit jit
    in/out shardings require exact divisibility; e.g. 25 heads on a 16-way
    axis, or a 32001 vocab)."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    parts = parts[: len(shape)]
    for i, p in enumerate(parts):
        if p is None:
            continue
        axes = p if isinstance(p, tuple) else (p,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if shape[i] % size != 0:
            parts[i] = None
    return P(*parts)


def sanitized_named(mesh, spec_tree, shape_tree):
    specs = jax.tree_util.tree_map(
        lambda sp, s: sanitize_spec(sp, s.shape, mesh), spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, P))
    return named(mesh, specs)


def param_structs(cfg, mesh, fsdp_axes):
    """abstract params + NamedSharding tree (no allocation)."""
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    specs = partition_tree(shapes, cfg.param_sharding, fsdp_axes, cfg=cfg,
                           model_size=mesh.shape.get("model"))
    shardings = sanitized_named(mesh, specs, shapes)
    structs = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)
    return structs, shardings


def batch_specs(cfg, shape, mesh, batch_axes, kind=None):
    """input ShapeDtypeStructs with batch sharded over the data-like axes."""
    specs = make_specs(cfg, shape, kind)
    b = P(batch_axes) if batch_axes else P(None)

    def shard_of(_path_leaf_name, leaf):
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(*( (batch_axes if batch_axes else None),
                                        *([None] * (leaf.ndim - 1)))))

    return {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                    sharding=shard_of(k, v))
            for k, v in specs.items()}


def cache_spec_tree(model: Model, cfg, shape, mesh, batch_axes):
    """Decode-cache ShapeDtypeStructs with shardings."""
    shapes = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                 prefill_len=shape.seq_len - 1))
    bd = batch_axes if batch_axes else None

    def trunc(nd, *parts):
        parts = tuple(parts)[:nd]
        parts = parts + (None,) * (nd - len(parts))
        return P(*parts)

    def spec_for(kp, leaf):
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
        nd = leaf.ndim
        stacked = isinstance(cfg.block_pattern, tuple) and len(cfg.block_pattern) == 1
        lead = (None,) if stacked else ()
        if "pos" in path:
            return trunc(nd)
        if "attn" in path:  # (L, B, S, KV, hd): shard head_dim (KV often < 16)
            return trunc(nd, *lead, bd, None, None, "model") if nd >= 4 else P()
        if "ssm_h" in path:  # (L, B, H, p, n)
            return trunc(nd, *lead, bd, "model", None, None)
        if "ssm_conv" in path:  # (L, B, K-1, di)
            return trunc(nd, *lead, bd, None, "model")
        if "mlstm_c" in path:  # (L?, B, H, dk, dv)
            return trunc(nd, *lead, bd, None, None, "model")
        if "mlstm_n" in path:
            return trunc(nd, *lead, bd, None, None)
        if "mlstm_m" in path:
            return trunc(nd, *lead, bd, None)
        if "slstm" in path:  # (B, d)
            return trunc(nd, bd, "model")
        return trunc(nd)

    specs = jax.tree_util.tree_map_with_path(spec_for, shapes)
    shardings = sanitized_named(mesh, specs, shapes)
    structs = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)
    return structs, shardings


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------
def make_sgld_train_step(model: Model, shape: ShapeConfig, mode: str = "sync",
                         gamma: float = 1e-5, sigma: float = 1e-6):
    """Full training step: microbatched grads + SGLD update.

    sync:     params' = params - gamma * g(params) + noise
    pipeline: params' = params - gamma * pending  + noise; pending' = g(params)
    """
    grad_fn = make_grad_fn(model, shape.num_microbatches)
    scale = (2.0 * sigma * gamma) ** 0.5

    if mode == "sync":
        def step(params, batch, key):
            grads, metrics = grad_fn(params, batch)
            noise = langevin_noise(key, params, jnp.float32(scale), jnp.float32)
            new_params = apply_update(params, grads, jnp.float32(gamma), noise)
            return new_params, metrics["loss"]
        return step

    if mode == "pipeline":
        def step(params, pending, batch, key):
            grads, metrics = grad_fn(params, batch)
            noise = langevin_noise(key, params, jnp.float32(scale), jnp.float32)
            new_params = apply_update(params, pending, jnp.float32(gamma), noise)
            return new_params, grads, metrics["loss"]
        return step

    raise ValueError(mode)


def make_prefill_step(model: Model):
    def step(params, batch):
        return model.prefill(params, batch)
    return step


def make_decode_step(model: Model):
    def step(params, cache, batch):
        return model.serve_step(params, cache, batch["tokens"], batch["cur_pos"])
    return step
