"""The CLI launcher end to end: ``repro.launch.train.main`` builds the
``samplers.sgld`` preset for each read model (and the fused commit), drives
it through the Engine on the reduced qwen3-4b, and moves every parameter
leaf to finite values."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.launch import train
from repro.models.transformer import init_params

TINY = ["--arch", "qwen3-4b", "--reduced", "--steps", "4", "--batch", "2",
        "--seq", "16", "--chunk", "2"]


@pytest.mark.parametrize("flags", [
    ["--mode", "sync"],
    ["--mode", "pipeline"],
    ["--mode", "consistent", "--tau", "2"],
    ["--mode", "inconsistent", "--tau", "2"],
    ["--mode", "consistent", "--tau", "2", "--fused"],
], ids=["sync", "pipeline", "consistent", "inconsistent", "consistent-fused"])
def test_launcher_moves_params_in_every_mode(flags, capsys, monkeypatch,
                                             tmp_path):
    # with the variable set, main() leaves the process's compile-cache
    # setting alone, so the tests after this one run as they would alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    state = train.main(TINY + flags)
    out = capsys.readouterr().out
    assert f"mode={flags[1]}" in out, out
    assert re.search(r"step +1 loss +\d", out), out  # the log hook fired
    assert int(state.step) == 4
    before = init_params(jax.random.PRNGKey(0), get_reduced("qwen3-4b"))
    moved = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.any(a != b)), before, state.params)
    assert all(jax.tree_util.tree_leaves(moved)), moved
    for leaf in jax.tree_util.tree_leaves(state.params):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()
