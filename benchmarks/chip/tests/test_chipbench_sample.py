"""CPU rehearsal of the sampling driver at a tiny size: a sound run is
correct; the float8 control and each fault planted in the timed path (or
in the reference put in its place) are not."""

import json
import os
import sys
import time
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import harness  # noqa: E402

#: limits for the tiny size at gamma 0.1 and sigma 1e-6, where a gradient
#: step shows through bfloat16 rounding, set from CPU readings (seeds 1-6):
#: the program's loss gap 1.9e-4..4.0e-4 and change gap 1.1e-3..1.5e-3; the
#: control's 6.5e-3..8.9e-3 and 0.67..0.77; the fresh read's change gap
#: 0.011..0.041, the zero gradient's 0.79..0.81, the half batch's 0.37..0.49
LIMITS = {"loss_gap": 0.002, "change_gap": 0.006}


def tiny():
    conf = harness.load_json(os.path.join(BENCH, "configs", "qwen3-4b.json"))
    conf.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, vocab_size=256)
    tr = harness.traffic_file("sample")
    tr.update(seq_len=32, sequences_per_commit=2, commits_per_chunk=3,
              chunks_per_call=1, pool_commits=16, gamma=0.1, sigma=1e-6,
              limits={conf["name"]: LIMITS})
    return conf, tr


def run_once(seed=2**31 + 77):
    import jax

    conf, tr = tiny()
    drv = harness.driver(tr)
    ctx = SimpleNamespace(conf=conf, cfg=harness.arch_config(conf),
                          traffic=tr, seed=seed, seconds=0.2, trace=False,
                          t_start=time.perf_counter(),
                          devices=jax.devices())
    return drv.run(ctx)


def test_sound_run_is_correct():
    out = run_once()
    assert out.correct, out.compared
    assert out.attempted > 0 and out.failed == 0
    assert set(out.e2e) == {"sample_tokens_per_s", "setup_s"}
    assert all(v > 0 for v in out.e2e.values())
    line = {"compared": {k: {"value": v, "limit": lim}
                         for k, (v, lim) in out.compared.items()}}
    assert json.loads(json.dumps(line)) == line


def test_control_is_not_correct():
    """The readings the limits are set from, judged at the limits as a run
    judges them: the program passes, the float8 control and every fault
    planted in the reference put in its place fail."""
    import jax

    import calibrate

    conf, tr = tiny()
    ctx = SimpleNamespace(conf=conf, cfg=harness.arch_config(conf),
                          traffic=tr, seed=5, devices=jax.devices())
    out = calibrate.sample_seed(harness.driver(tr), ctx, LIMITS, True)
    assert out["program"]["correct"], out["program"]
    for name in ("control", "half_batch", "zero_grad", "fresh_read"):
        assert not out[name]["correct"], (name, out[name])


def test_unchanged_state_is_not_correct(monkeypatch):
    from repro.samplers import transforms

    monkeypatch.setattr(transforms, "fused_langevin_update",
                        lambda params, *a: params)
    out = run_once()
    assert not out.correct
    assert out.compared["change_gap"][0] > 0.9


def test_half_batch_is_not_correct(monkeypatch):
    from repro.train import loop

    real = loop.make_grad_fn

    def half(model, *a, **k):
        grad = real(model, *a, **k)

        def cut(params, batch):
            t = batch["tokens"]
            return grad(params, dict(batch, tokens=t[:t.shape[0] // 2]))
        return cut

    monkeypatch.setattr(loop, "make_grad_fn", half)
    out = run_once()
    assert not out.correct, out.compared


def test_zero_gradient_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.samplers import presets

    real = presets.gradients

    def zeroed(*a, **k):
        stage = real(*a, **k)

        def update(ctx, state):
            ctx, state = stage.update(ctx, state)
            return ctx._replace(grads=jax.tree_util.tree_map(
                jnp.zeros_like, ctx.grads)), state
        return stage._replace(update=update)

    monkeypatch.setattr(presets, "gradients", zeroed)
    out = run_once()
    assert not out.correct, out.compared


def test_fresh_read_is_not_correct(monkeypatch):
    from repro.samplers import presets

    real = presets.delay_read

    def fresh(*a, **k):
        stage = real(*a, **k)

        def update(ctx, ring):
            ctx, ring = stage.update(ctx, ring)
            return ctx._replace(x_hat=ctx.params), ring
        return stage._replace(update=update)

    monkeypatch.setattr(presets, "delay_read", fresh)
    out = run_once()
    assert not out.correct, out.compared


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_seeds_above_32_bits_count(seed):
    import numpy as np

    from chipbench.harness import base_key

    assert not np.array_equal(np.asarray(base_key(seed)),
                              np.asarray(base_key(seed + 2**32)))
