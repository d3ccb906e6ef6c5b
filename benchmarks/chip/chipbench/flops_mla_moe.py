"""Operations and bytes of a DeepSeek-V3 decoder's sampling commit (latent
attention, leading dense layers, sigmoid-routed experts held here), from a
configuration file's shapes (HF key names) and the routed work a run
counted.

FLOPs count a multiply-add as two; nothing is counted twice for
recomputation; attention is causal, a token attending to itself and the
tokens before it; the backward costs twice the forward.  The routed
experts' work is not a function of the shapes: it is the (token, expert)
assignments the router sent to the experts held here, which the program
counts (``moe.assignments_held``) and the driver passes on.
"""

from __future__ import annotations


def _mla_params(conf: dict) -> int:
    """Matmul weights of one latent-attention block (no q LoRA)."""
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    nope, rope = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    v, r = conf["v_head_dim"], conf["kv_lora_rank"]
    return (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + v)
            + H * v * d)


def dense_layers(conf: dict) -> int:
    return conf["first_k_dense_replace"]


def moe_layers(conf: dict) -> int:
    return conf["num_hidden_layers"] - conf["first_k_dense_replace"]


def token_matmul_params(conf: dict) -> int:
    """Matmul weights every token meets whatever the router does: latent
    attention in every layer, the dense layers' MLP, each MoE layer's
    router (all its outputs) and shared experts, and the head."""
    d = conf["hidden_size"]
    shared = 3 * d * conf["moe_intermediate_size"] * conf["n_shared_experts"]
    router = d * conf["deployment"]["router_outputs"]
    return (conf["num_hidden_layers"] * _mla_params(conf)
            + dense_layers(conf) * 3 * d * conf["intermediate_size"]
            + moe_layers(conf) * (router + shared)
            + d * conf["vocab_size"])


def expert_params(conf: dict) -> int:
    """Weights one routed (token, expert) assignment multiplies through."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def sum_attention_flops(conf: dict, seq: int) -> int:
    """Forward attention FLOPs of one causal sequence, all layers: scores
    over q/k heads of nope + rope, the weighted sum over v heads."""
    H = conf["num_attention_heads"]
    width = conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"] + \
        conf["v_head_dim"]
    return conf["num_hidden_layers"] * 2 * H * width * seq * (seq + 1) // 2


def train_flops(conf: dict, sequences: int, seq: int,
                held_assignments: int) -> int:
    """Forward and backward FLOPs of ``sequences`` training sequences of
    ``seq`` predicted tokens whose routers sent ``held_assignments``
    (token, expert) pairs, over all MoE layers, to the experts held here."""
    fwd = (2 * token_matmul_params(conf) * sequences * seq
           + sequences * sum_attention_flops(conf, seq)
           + 2 * expert_params(conf) * held_assignments)
    return 3 * fwd


def gmm_call(conf: dict, rows: float) -> tuple:
    """``(FLOPs, bytes)`` of one grouped-matmul kernel call over ``rows``
    routed rows: every call (an expert projection forward, its input
    gradient, its weight gradient) multiplies each row through one
    hidden x expert-width matrix, reads the rows and the held experts'
    matrices and writes its result, in bfloat16."""
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    held = conf["n_routed_experts"]
    return 2.0 * rows * d * f, 2.0 * (rows * (d + f) + held * d * f)
