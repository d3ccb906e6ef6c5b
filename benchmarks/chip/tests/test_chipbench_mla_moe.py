"""The MoE sampling cell on the CPU: its FLOP counts against hand-worked
values, the driver's reading of the configuration file, and a rehearsal
of the driver at a tiny size (a sound run is correct; the float8 control
and the planted faults are not)."""

import os
import sys
import time
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import flops_mla_moe, harness  # noqa: E402

NAME = "moonlight-16b-a3b"


def conf():
    return harness.load_json(os.path.join(BENCH, "configs", NAME + ".json"))


def driver():
    return harness.driver(harness.traffic_file("sample-8k"))


def test_matmul_parameters_by_hand():
    c = conf()
    # MLA: q 2048 x 16*192, kv_a 2048 x 576, kv_b 512 x 16*256, o 2048 x 2048
    mla = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert mla == 13_762_560
    # 5 MLA blocks, the dense MLP 3 x 2048 x 11264, 4 MoE layers' routers
    # (64 outputs) and shared experts (3 x 2048 x 2816), the head slice
    per_token = 5 * mla + 3 * 2048 * 11264 + 4 * (2048 * 64
                                                  + 3 * 2048 * 2816) \
        + 2048 * 20480
    assert flops_mla_moe.token_matmul_params(c) == per_token
    assert flops_mla_moe.expert_params(c) == 3 * 2048 * 1408


def test_commit_flops_by_hand():
    c = conf()
    seq, held = 8192, 4 * 16384 * 6 // 8
    attn = 5 * 2 * 16 * (192 + 128) * seq * (seq + 1) // 2
    want = 3 * (2 * flops_mla_moe.token_matmul_params(c) * 2 * seq
                + 2 * attn + 2 * 3 * 2048 * 1408 * held)
    assert flops_mla_moe.train_flops(c, 2, seq, held) == want
    # about 37.4 TFLOP a commit of 2 x 8192 tokens at the mean held load
    assert 37 < want / 1e12 < 38


def test_gmm_call_by_hand():
    op, nbytes = flops_mla_moe.gmm_call(conf(), 100)
    assert op == 2 * 100 * 2048 * 1408
    assert nbytes == 2 * (100 * (2048 + 1408) + 8 * 2048 * 1408)


def test_program_config_from_the_file():
    drv = driver()
    cfg = drv.program_config(conf())
    assert (cfg.num_experts, cfg.num_held, cfg.expert_offset) == (64, 8, 0)
    assert (cfg.d_ff, cfg.dense_d_ff, cfg.first_k_dense) == (1408, 11264, 1)
    assert (cfg.kv_lora_rank, cfg.head_dim, cfg.v_head_dim) == (512, 192, 128)
    assert cfg.router_score == "sigmoid" and cfg.router_aux_coef == 0.0
    assert cfg.routed_scaling_factor == 2.446 and not cfg.tie_embeddings
    bias = cfg.score_correction_bias
    assert len(bias) == 4 and all(len(row) == 64 for row in bias)
    for layer, hot in enumerate((1, 3, 5, 7)):
        assert bias[layer][hot] == 0.1 and sum(bias[layer]) == 0.1
    # harness.arch_config's dense reading of the same file is looked up again
    assert drv.as_program(harness.arch_config(conf())) == cfg


def test_parameters_per_chip():
    """568.5 M parameters, as the file's sizing states."""
    import jax
    import numpy as np

    from chipbench.weights import layout

    shapes = layout(driver().program_config(conf()))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert abs(n / 1e6 - 568.5) < 0.1, n


def test_unsupported_routing_is_refused():
    c = dict(conf(), n_group=8)
    with pytest.raises(ValueError, match="n_group"):
        driver().program_config(c)


#: limits for the tiny size at gamma 0.1 and sigma 1e-6, the program in
#: float32, set from CPU readings (seeds 1-6): the program's loss gap
#: 0..1.9e-6 and change gap 2.0e-7..7.9e-7; the float8 control's
#: 0.017..0.035 and 1.38..1.74; the half batch's 0.058..0.19 and 0.34..0.55,
#: the zero gradient's change gap 0.97, the fresh read's 0.083..0.13.  In
#: bfloat16 a tiny model's 64 tokens a commit flip their routing choices on
#: rounding often enough that the program's gaps (0.007..0.074 and
#: 0.009..0.043) reach the control's; at the cell's 16,384 tokens that is
#: the chip's calibration to read.
LIMITS = {"loss_gap": 1e-4, "change_gap": 1e-4}


def tiny():
    c = conf()
    c.update(name="moonlight-tiny", hidden_size=64, intermediate_size=128,
             num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             moe_intermediate_size=32, n_routed_experts=4,
             num_experts_per_tok=3, num_hidden_layers=3, vocab_size=256,
             score_correction_bias={"hot_expert_by_moe_layer": [1, 3],
                                    "hot": 0.1},
             deployment=dict(c["deployment"], router_outputs=8,
                             first_expert=2), torch_dtype="float32")
    tr = harness.traffic_file("sample-8k")
    tr.update(seq_len=32, sequences_per_commit=2, commits_per_chunk=3,
              chunks_per_call=1, pool_commits=16, limits={c["name"]: LIMITS})
    drv = driver()
    drv.CONFS[c["name"]] = c
    return drv, c, tr


def test_sound_run_is_correct():
    import jax

    drv, c, tr = tiny()
    ctx = SimpleNamespace(conf=c, cfg=harness.arch_config(c), traffic=tr,
                          seed=2**31 + 77, seconds=0.2, trace=False,
                          t_start=time.perf_counter(), devices=jax.devices())
    out = drv.run(ctx)
    assert out.correct, out.compared
    assert out.attempted > 0 and out.failed == 0
    assert out.layer["held_assignments"] > 0
    # every held expert's (token, expert) pairs, none dropped: the window's
    # held assignments are at most its commits x tokens x k x layers
    assert out.layer["held_assignments"] <= (
        out.attempted * 2 * 32 * 3 * 2)


def test_control_is_not_correct():
    import jax

    import calibrate

    drv, c, tr = tiny()
    ctx = SimpleNamespace(conf=c, cfg=harness.arch_config(c), traffic=tr,
                          seed=5, devices=jax.devices())
    out = calibrate.sample_seed(drv, ctx, LIMITS, True)
    assert out["program"]["correct"], out["program"]
    for name in ("control", "half_batch", "zero_grad", "fresh_read"):
        assert not out[name]["correct"], (name, out[name])
