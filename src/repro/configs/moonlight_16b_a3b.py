"""moonlight-16b-a3b — DeepSeek-V3 block: latent attention and sigmoid-routed
experts [hf:moonshotai/Moonlight-16B-A3B config.json].

Source: https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json
(``model_type`` deepseek_v3).  27L d_model=2048 16H; MLA with no q LoRA,
kv_lora_rank=512, q/k heads 128 nope + 64 rope, v heads 128, rope theta
50,000; layer 0 dense (MLP 11,264, ``first_k_dense_replace`` 1), then 26 MoE
layers of 64 routed experts of 1,408 (6 per token) and 2 shared experts;
``scoring_func`` sigmoid, ``topk_method`` noaux_tc, ``n_group`` =
``topk_group`` = 1, ``norm_topk_prob``, ``routed_scaling_factor`` 2.446;
vocab 163,840, head untied, RMSNorm eps 1e-5.  The trained
``e_score_correction_bias`` is a buffer of the checkpoint; without one it
reads as zeros here.
"""

from dataclasses import replace

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,                  # q and k heads: 128 nope + 64 rope
    d_ff=1408,                     # moe_intermediate_size
    vocab_size=163840,
    rope_theta=50_000.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=64,
    experts_per_token=6,
    num_shared_experts=2,
    router_aux_coef=0.0,           # noaux_tc: the loss is cross-entropy alone
    router_score="sigmoid",
    routed_scaling_factor=2.446,
    first_k_dense=1,
    dense_d_ff=11264,
    norm_eps=1e-5,
    block_pattern=("attn_moe",),
)


def reduced() -> ArchConfig:
    """1 dense + 2 MoE layers at CPU widths, every mechanism kept."""
    return replace(CONFIG, name=CONFIG.name + "-reduced", num_layers=3,
                   d_model=64, num_heads=4, num_kv_heads=4, head_dim=24,
                   kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                   v_head_dim=16, d_ff=32, dense_d_ff=128, vocab_size=512,
                   num_experts=8, experts_per_token=3)
