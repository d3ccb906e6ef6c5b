"""Operations and bytes the algorithms need, from a configuration's shapes.

Everything takes the configuration file's dict (HF key names).  FLOPs count
a multiply-add as two.  Nothing is counted twice for recomputation, and
attention is causal: a token attends to itself and the tokens before it.
"""

from __future__ import annotations


def _dims(conf: dict):
    d = conf["hidden_size"]
    hd = conf["head_dim"]
    return (d, conf["num_attention_heads"] * hd,
            conf["num_key_value_heads"] * hd, conf["intermediate_size"],
            conf["vocab_size"], conf["num_hidden_layers"])


def layer_matmul_params(conf: dict) -> int:
    """Weights one token multiplies through in one decoder layer: the
    q, k, v and output projections and the three SwiGLU matrices."""
    d, q, kv, f, _, _ = _dims(conf)
    return d * q + 2 * d * kv + q * d + 3 * d * f


def head_params(conf: dict) -> int:
    d, _, _, _, v, _ = _dims(conf)
    return d * v


def matmul_params(conf: dict) -> int:
    """Every matmul weight a token meets, the output head included (the
    embedding lookup is no matmul)."""
    return conf["num_hidden_layers"] * layer_matmul_params(conf) + \
        head_params(conf)


def attention_flops(conf: dict, context: int) -> int:
    """Forward FLOPs of one query over ``context`` keys, all layers: the
    scores and the weighted sum of values."""
    d, q, _, _, _, layers = _dims(conf)
    return layers * 4 * q * context


def sum_attention_flops(conf: dict, seq: int) -> int:
    """Forward attention FLOPs of a causal sequence: token ``t`` (from 1)
    attends to ``t`` positions."""
    return attention_flops(conf, 1) * seq * (seq + 1) // 2


def train_flops_per_sequence(conf: dict, seq: int) -> int:
    """Forward and backward FLOPs of one training sequence of ``seq``
    predicted tokens (the backward costs twice the forward)."""
    mm = 2 * matmul_params(conf) * seq
    attn = sum_attention_flops(conf, seq)
    return 3 * (mm + attn)


def langevin_bytes(leaves) -> int:
    """HBM bytes of one fused SGLD update over ``leaves`` (pairs of element
    count and bytes per element): x and g read, x written, each in the
    leaf's dtype."""
    return sum(3 * n * b for n, b in leaves)


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak HBM bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])

