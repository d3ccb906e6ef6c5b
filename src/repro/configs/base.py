"""Config system: architecture + input-shape dataclasses and the registry.

Every assigned architecture gets one module in ``repro/configs/`` defining an
``ArchConfig`` with the exact assigned hyper-parameters (source cited) plus a
``reduced()`` variant for CPU smoke tests.  Select with ``--arch <id>``.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass, replace
from typing import Optional

ARCH_IDS = [
    "hymba_1p5b",
    "minicpm_2b",
    "internvl2_1b",
    "kimi_k2_1t_a32b",
    "phi35_moe_42b_a6p6b",
    "xlstm_1p3b",
    "qwen3_4b",
    "stablelm_12b",
    "qwen15_32b",
    "musicgen_medium",
    "moonlight_16b_a3b",
]

# canonical dashed ids (CLI) -> module names
ALIASES = {
    "hymba-1.5b": "hymba_1p5b",
    "minicpm-2b": "minicpm_2b",
    "internvl2-1b": "internvl2_1b",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "phi3.5-moe-42b-a6.6b": "phi35_moe_42b_a6p6b",
    "xlstm-1.3b": "xlstm_1p3b",
    "qwen3-4b": "qwen3_4b",
    "stablelm-12b": "stablelm_12b",
    "qwen1.5-32b": "qwen15_32b",
    "musicgen-medium": "musicgen_medium",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}


@dataclass(frozen=True)
class ArchConfig:
    """Architecture hyper-parameters (transformer backbone)."""

    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    source: str                    # citation for the config numbers
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # default: d_model // num_heads

    # attention variants
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    sliding_window: Optional[int] = None  # static window if set

    # latent attention (MLA, DeepSeek-V2/V3): k and v from a compressed
    # latent of kv_lora_rank plus one rope key shared by all heads; q and k
    # heads are qk_nope_head_dim + qk_rope_head_dim wide, v heads
    # v_head_dim.  kv_lora_rank 0: ordinary multi-head attention.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # MoE (d_ff is one expert's width; the shared experts are one MLP of
    # d_ff * num_shared_experts)
    num_experts: int = 0           # routed experts the router scores
    experts_per_token: int = 0
    num_shared_experts: int = 0
    router_aux_coef: float = 0.01
    # "softmax" (top-k of the softmax, Switch aux loss) or "sigmoid"
    # (DeepSeek-V3 noaux_tc: top-k of sigmoid + score_correction_bias, gates
    # the unbiased scores, no aux loss); gates are normalised over the k
    router_score: str = "softmax"
    routed_scaling_factor: float = 1.0
    # per-MoE-layer e_score_correction_bias rows (num_experts floats each),
    # a fixed buffer outside the sampled parameters; () reads as zeros
    score_correction_bias: tuple = ()
    # the experts this device holds: experts_held (0: all) starting at
    # expert_offset.  The layer routes over all num_experts and returns the
    # held experts' part of the result plus the shared experts.
    experts_held: int = 0
    expert_offset: int = 0
    # leading dense layers (DeepSeek first_k_dense_replace) before the
    # block_pattern stack, with an MLP of dense_d_ff
    first_k_dense: int = 0
    dense_d_ff: int = 0

    # SSM (mamba-style heads: hymba) / xLSTM
    ssm_state: int = 0
    ssm_conv: int = 4
    block_pattern: tuple = ("attn_mlp",)  # cycled over layers

    # misc
    act: str = "silu"
    residual_scale: float = 1.0     # MiniCPM depth-scaled residuals
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    frontend: Optional[str] = None  # None | "vision" | "audio"
    num_frontend_tokens: int = 0    # prepended stub-embedding positions
    dtype: str = "bfloat16"

    # distribution
    param_sharding: str = "tp"      # "tp" | "fsdp_tp" (2-D for trillion-scale)

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        assert self.num_heads % self.num_kv_heads == 0 or self.num_kv_heads == 0
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router_score {self.router_score!r}")
        if self.experts_held and (
                self.expert_offset < 0 or self.expert_offset
                + self.experts_held > self.num_experts):
            last = self.expert_offset + self.experts_held - 1
            raise ValueError(f"experts {self.expert_offset}..{last} are not "
                             f"among {self.num_experts}")

    # -- derived -------------------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def num_held(self) -> int:
        """Routed experts held on this device."""
        return self.experts_held or self.num_experts

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    def param_count(self) -> int:
        """Exact parameter count via eval_shape of the real init (cached)."""
        if not hasattr(self, "_pcount"):
            import jax  # local: keep configs importable without device init
            import numpy as np
            from repro.models.transformer import init_params

            shapes = jax.eval_shape(lambda k: init_params(k, self),
                                    jax.ShapeDtypeStruct((2,), "uint32"))
            n = sum(int(np.prod(x.shape))
                    for x in jax.tree_util.tree_leaves(shapes))
            object.__setattr__(self, "_pcount", n)
        return self._pcount

    def active_param_count(self) -> int:
        """Activated params per token (MoE: only routed experts)."""
        full = self.param_count()
        if self.num_experts == 0:
            return full
        d = self.d_model
        expert_p = 3 * d * self.d_ff
        n_moe_layers = sum(
            1 for i in range(self.num_moe_layers)
            if self.block_pattern[i % len(self.block_pattern)] == "attn_moe")
        inactive = ((self.num_held - self.experts_per_token)
                    * expert_p * n_moe_layers)
        return int(full - inactive)


@dataclass(frozen=True)
class ShapeConfig:
    """An assigned (input shape x step kind)."""

    name: str
    seq_len: int
    global_batch: int
    kind: str            # "train" | "prefill" | "decode"


def get_arch(name: str) -> ArchConfig:
    mod_name = ALIASES.get(name, name).replace("-", "_").replace(".", "p")
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.CONFIG


def get_reduced(name: str) -> ArchConfig:
    mod_name = ALIASES.get(name, name).replace("-", "_").replace(".", "p")
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.reduced()


def all_archs() -> list[ArchConfig]:
    return [get_arch(a) for a in ARCH_IDS]


def _reduce_common(cfg: ArchConfig, **over) -> ArchConfig:
    """Shared recipe for CPU smoke variants: 2 layers, d_model<=512, <=4 experts."""
    kw = dict(
        num_layers=2,
        d_model=256,
        num_heads=4,
        num_kv_heads=max(1, min(cfg.num_kv_heads, 2)),
        head_dim=64,
        d_ff=512,
        vocab_size=512,
        sliding_window=min(cfg.sliding_window, 64) if cfg.sliding_window else None,
    )
    if cfg.num_experts:
        kw.update(num_experts=4, experts_per_token=2,
                  num_shared_experts=min(cfg.num_shared_experts, 1))
    kw.update(over)
    return replace(cfg, name=cfg.name + "-reduced", **kw)
