"""FLOP and byte counts of the chip benchmark against hand-worked values."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import flops  # noqa: E402


def conf(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_qwen3_4b_matmul_parameters():
    c = conf("qwen3-4b")
    # per layer: q 2560x4096, k and v 2560x1024, o 4096x2560, MLP 3 x
    # 2560x9728; two layers and the 2560x151936 head (tied: the embedding's
    # transpose, still a matmul)
    per_layer = 2560 * 4096 * 2 + 2560 * 1024 * 2 + 3 * 2560 * 9728
    assert per_layer == 100_925_440
    assert flops.layer_matmul_params(c) == per_layer
    assert flops.matmul_params(c) == 2 * per_layer + 2560 * 151936
    assert flops.matmul_params(c) == 590_807_040


@pytest.mark.parametrize("seq", [1, 2, 7, 512])
def test_causal_attention_flops_sum(seq):
    c = conf("qwen3-4b")
    by_hand = sum(2 * 4 * 4096 * t for t in range(1, seq + 1))
    assert flops.sum_attention_flops(c, seq) == by_hand


def test_qwen3_4b_commit_flops():
    c = conf("qwen3-4b")
    one = flops.train_flops_per_sequence(c, 1024)
    assert one == 3 * (2 * 590_807_040 * 1024
                       + 2 * 4 * 4096 * 1024 * 1025 // 2)
    # 4 sequences of 1024 tokens a commit: about 14.73 TFLOP
    assert abs(4 * one / 1e12 - 14.73) < 0.01


def test_qwen3_4b_update_bytes():
    """The fused update's bytes over the program's own parameter layout:
    the head is the embedding's transpose, so no leaf of its own."""
    import jax

    from chipbench import harness
    from chipbench.weights import layout

    c = conf("qwen3-4b")
    leaves = [(int(x.size), x.dtype.itemsize)
              for x in jax.tree_util.tree_leaves(
                  layout(harness.arch_config(c)))]
    # embedding 151936x2560; per layer the matmuls, two norms of 2560 and
    # the q and k norms of 128; the final norm
    per_layer = 100_925_440 + 2 * 2560 + 2 * 128
    assert sum(n for n, _ in leaves) == 151936 * 2560 + 2 * per_layer + 2560
    # the norm scales (13,312 elements) are float32, the rest bfloat16
    norms = 2560 + 2 * (2 * 2560 + 2 * 128)
    assert flops.langevin_bytes(leaves) == 3 * (2 * (590_820_352 - norms)
                                                + 4 * norms)


def test_langevin_bytes_and_roofline():
    leaves = [(10, 2), (3, 4)]
    assert flops.langevin_bytes(leaves) == 3 * (20 + 12)
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time_s(50.0, 20.0, peak) == 2.0
    assert flops.least_time_s(500.0, 20.0, peak) == 5.0
