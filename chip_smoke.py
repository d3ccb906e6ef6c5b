"""Smoke run of the system's main path on TPU v5e chips.

Delayed-gradient sampling of qwen3-4b at its published widths (depth cut to
2 layers) through ``ClusterEngine``, then Bayesian-model-average (BMA)
serving of the chain bank it produced through ``PagedDecodeEngine``:

    python chip_smoke.py              # one chip: sampling, then serving
    python chip_smoke.py --chips 4    # four chips: 4 chains sharded one per
                                      # chip against each chain run alone,
                                      # then sharded serving

Weights and data are random, made from ``--seed``.  Every check raises on
failure; the lines before the last are smoke output, not benchmark
metrics.  The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, naming the devices the run used.  Where
JAX finds no TPU the script exits nonzero before any phase.

Sizing (one v5e chip, 16 GiB of HBM): one copy of qwen3-4b costs 1.449 GiB
in bf16 for the untied embedding and head plus 0.188 GiB per layer, so
1.825 GiB at 2 layers.  A W-Con chain holds its parameters, the tau+1
iterate ring and the gradients: tau+3 copies, 9.1 GiB at tau=2 before
activations.  Hence one chain per chip at tau=2 and 2 layers; the phase
prints the compiled chunk's ``memory_analysis()`` against the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import replace

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "qwen3-4b"
DEPTH = 2            # layers kept of 36
TAU = 2              # W-Con staleness bound (ring depth tau+1)
SEQ = 1024           # tokens per training sequence
BATCH = 4            # sequences per commit
COMMITS = 4          # commits per chunk
CHUNKS = 2
GAMMA = 1e-3
SIGMA = 1e-4         # Langevin temperature; noise sqrt(2*SIGMA*GAMMA)
PROMPTS = (64, 200, 333, 512)  # prompt lengths of the serving requests
NEW_TOKENS = 16
PAGE = 16
MAX_SEQ = 1024       # paged slot capacity, a multiple of PAGE
# Chains run sharded and alone are two programs.  One commit rounds the
# update into bf16 parameters once, so a gradient summed in another order
# can move an element by one ulp; over CHUNKS*COMMITS commits such flips
# stay rare.  A wrong key, batch, read version or chain changes nearly
# every element.
MAX_DIFF_FRACTION = 1e-3


def smoke_config():
    """qwen3-4b at its published widths, cut to :data:`DEPTH` layers."""
    from repro.configs import get_arch

    full = get_arch(ARCH)
    cfg = replace(full, num_layers=DEPTH)
    widths = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
              "vocab_size")
    if [getattr(cfg, w) for w in widths] != [2560, 32, 8, 128, 9728, 151936]:
        raise AssertionError(f"not the published widths: {cfg}")
    return cfg


def train_shape():
    from repro.configs import ShapeConfig

    return ShapeConfig("smoke", seq_len=SEQ, global_batch=BATCH, kind="train")


def sampling_engine(cfg, chains: int, mesh=None):
    """``(model, engine)``: the W-Con fused-update sampler over ``chains``
    chains, fed explicit per-chain batches."""
    from repro import samplers
    from repro.cluster import ClusterEngine
    from repro.models.transformer import Model
    from repro.train.loop import make_grad_fn

    model = Model(cfg)
    sampler = samplers.sgld("consistent", make_grad_fn(model), has_aux=True,
                            tau=TAU, fused=True, gamma=GAMMA, sigma=SIGMA)
    engine = ClusterEngine(sampler, num_chains=chains, chunk_size=COMMITS,
                           per_chain_batches=True, collect_aux=True,
                           mesh=mesh)
    return model, engine


def make_batches(cfg, seed: int, chains: int):
    """``(CHUNKS*COMMITS, chains, BATCH, SEQ+1)`` token batches from
    ``make_batch``, one key per (commit, chain)."""
    import jax

    from repro.data import make_batch

    steps = CHUNKS * COMMITS
    keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), 1),
                            steps * chains).reshape(steps, chains, -1)
    shape = train_shape()
    return jax.vmap(jax.vmap(lambda k: make_batch(cfg, shape, k, "train")))(
        keys)


def chunk_shapes(cfg, engine, chains: int, mesh=None):
    """Abstract ``(state, batches, extra)`` of one sampling chunk, for
    :meth:`ClusterEngine.lower_chunk`; with ``mesh``, placed the way the
    sharded run places them (chain axis over ``data``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models.transformer import init_params

    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: init_params(k, cfg), key)
    state = jax.eval_shape(engine.init, params, key)
    batches = jax.eval_shape(lambda: make_batches(cfg, 0, chains))
    batches = jax.tree_util.tree_map(
        lambda b: jax.ShapeDtypeStruct((COMMITS,) + b.shape[1:], b.dtype),
        batches)
    extra = {"rv": jax.ShapeDtypeStruct((COMMITS, chains), jnp.int32)}
    if mesh is None:
        return state, batches, extra

    def place(tree, spec):
        return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)

    return (place(state, P("data")), place(batches, P(None, "data")),
            place(extra, P(None, "data")))


def async_schedules(chains: int, seed: int):
    """The first ``ensemble_async`` schedule set from ``seed`` on whose every
    chain the staleness reaches :data:`TAU` and stays within it."""
    from repro.cluster import ensemble_async
    from repro.core import WorkerModel

    for s in range(seed, seed + 100):
        scheds = ensemble_async(WorkerModel(num_workers=TAU, seed=s),
                                CHUNKS * COMMITS, chains, seed=s)
        if all(x.max_delay == TAU for x in scheds):
            return scheds
    raise RuntimeError(f"no schedule reaching tau={TAU} from seed {seed}")


def peak_gib(device) -> float:
    return device.memory_stats()["peak_bytes_in_use"] / 2**30


def bytes_limit(device) -> int:
    return device.memory_stats()["bytes_limit"]


def require_kernel(compiled, what: str) -> None:
    """Fail unless the compiled program runs a Pallas kernel compiled for
    the chip (not the interpreter)."""
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"the {what} holds no Pallas kernel")


def run_sampling(engine, cfg, seed: int):
    """Init and run the engine's chains for CHUNKS chunks; returns ``(init
    params (host), initial chain keys, final state, losses (steps, C),
    schedules, batches)``."""
    import jax
    import numpy as np

    from repro.models.transformer import init_params

    chains = engine.num_chains
    params = init_params(jax.random.PRNGKey(seed), cfg)
    host0 = jax.device_get(params)
    state = engine.init(params, jax.random.PRNGKey(seed + 1))
    keys0 = np.asarray(state.key)
    del params
    scheds = async_schedules(chains, seed)
    batches = make_batches(cfg, seed, chains)
    t0 = time.perf_counter()
    state, aux = engine.run(state, steps=CHUNKS * COMMITS, schedule=scheds,
                            batches=batches)
    losses = np.asarray(aux["loss"])
    print(f"smoke: sampling {chains} chain(s) x {CHUNKS * COMMITS} commits "
          f"in {time.perf_counter() - t0:.1f}s (compile included), "
          f"staleness per chain {[int(s.delays.max()) for s in scheds]}")
    if not np.isfinite(losses).all():
        raise AssertionError(f"non-finite loss {losses}")
    return host0, keys0, state, losses, scheds, batches


def sampling_phase(cfg, seed: int):
    """One chain at tau on one chip; checks losses, movement, the fused
    kernel in the compiled chunk, and the chunk's memory against the chip."""
    import jax
    import numpy as np

    dev = jax.devices()[0]
    model, engine = sampling_engine(cfg, 1)
    t0 = time.perf_counter()
    compiled = engine.lower_chunk(*chunk_shapes(cfg, engine, 1)).compile()
    print(f"smoke: sampling chunk compiled in "
          f"{time.perf_counter() - t0:.1f}s")
    require_kernel(compiled, "sampling chunk")
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    limit = bytes_limit(dev)
    print(f"smoke: C=1 tau={TAU} depth={DEPTH} seq={SEQ} batch={BATCH}: "
          f"chunk arguments {ma.argument_size_in_bytes / 2**30:.3f} GiB, "
          f"temporaries {ma.temp_size_in_bytes / 2**30:.3f} GiB, "
          f"needs {need / 2**30:.3f} of {limit / 2**30:.3f} GiB")
    if need > limit:
        raise AssertionError("the sampling chunk does not fit the chip")

    host0, _, state, losses, _, _ = run_sampling(engine, cfg, seed)
    print(f"smoke: losses {np.round(losses[:, 0], 4).tolist()}, "
          f"peak_bytes_in_use {peak_gib(dev):.3f} GiB")
    moved = [float(np.mean(np.asarray(new[0]) != old))
             for new, old in zip(jax.tree_util.tree_leaves(state.params),
                                 jax.tree_util.tree_leaves(host0))]
    print(f"smoke: fraction of parameters moved per leaf: min "
          f"{min(moved):.4f} max {max(moved):.4f}")
    if min(moved) == 0.0:
        raise AssertionError("a parameter leaf never moved")
    return model, state


def requests(cfg, seed: int):
    import numpy as np

    from repro.cluster.api import Request

    rng = np.random.default_rng(seed)
    return [Request(tokens=rng.integers(0, cfg.vocab_size, (t,),
                                        dtype=np.int32),
                    max_new_tokens=NEW_TOKENS) for t in PROMPTS]


def serve(model, bank, seed: int, mesh=None):
    """Serve the requests from ``bank`` through the fused paged engine;
    returns ``(engine, requests, completions in request order)``."""
    import numpy as np

    from repro.cluster import PagedDecodeEngine
    from repro.cluster.api import FINISH_LENGTH, STATUS_OK

    eng = PagedDecodeEngine.from_cluster(
        bank, model, num_slots=len(PROMPTS), page_size=PAGE,
        max_seq=MAX_SEQ, prompt_buckets=(max(PROMPTS),), mesh=mesh,
        fused=True, return_logits=True)
    reqs = requests(model.cfg, seed)
    t0 = time.perf_counter()
    ids = [eng.submit(r) for r in reqs]
    done = {c.request_id: c for c in eng.drain()}
    comps = [done[i] for i in ids]
    n_tok = sum(len(c.tokens) for c in comps)
    print(f"smoke: served {len(comps)} requests, {n_tok} tokens in "
          f"{time.perf_counter() - t0:.1f}s (compile included)")
    for c in comps:
        if (c.status != STATUS_OK or c.finish_reason != FINISH_LENGTH
                or c.tokens.shape != (NEW_TOKENS,)
                or c.logits.shape != (NEW_TOKENS, model.cfg.vocab_size)
                or not np.isfinite(c.logits).all()):
            raise AssertionError(f"request {c.request_id}: {c.status} "
                                 f"{c.finish_reason} {c.tokens.shape}")
    require_kernel(eng.lower_step().compile(), "paged decode step")
    return eng, reqs, comps


def reference_logp(model, bank, reqs, comps):
    """BMA log-probabilities of every generated position from
    ``Model.forward`` over prompt plus generated prefix, each chain on the
    first device at ``highest`` matmul precision: ``(R, NEW_TOKENS, V)``,
    and the largest |logit| of any chain."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import bma_logits

    width = max(PROMPTS) + NEW_TOKENS
    toks = np.zeros((len(reqs), width), np.int32)
    pos = np.zeros((len(reqs), NEW_TOKENS), np.int32)
    for i, (r, c) in enumerate(zip(reqs, comps)):
        seq = np.concatenate([r.tokens, c.tokens[:-1]])
        toks[i, :len(seq)] = seq  # right pad: causally invisible
        pos[i] = len(r.tokens) - 1 + np.arange(NEW_TOKENS)

    @jax.jit
    def chain_logits(params, toks, pos):
        logits, _, _ = model.forward(params, {"tokens": toks})
        return jnp.take_along_axis(logits, pos[..., None], axis=1).astype(
            jnp.float32)

    dev0 = jax.devices()[0]
    per_chain = []
    n = jax.tree_util.tree_leaves(bank)[0].shape[0]
    with jax.default_matmul_precision("highest"):
        for c in range(n):
            params = jax.device_put(
                jax.tree_util.tree_map(lambda x: x[c], bank), dev0)
            per_chain.append(chain_logits(params, toks, pos))
            del params
    logits = jnp.stack(per_chain)
    return np.asarray(bma_logits(logits)), float(jnp.abs(logits).max())


def check_logits(comps, ref) -> float:
    """Largest |BMA log-prob - reference| over every served position.  The
    tolerance counts bf16 ulps of logits below ``LOGIT_BOUND``, so the
    reference's logits must stay below it."""
    import numpy as np

    from repro.models.predictive import LOGIT_BOUND, LOGP_ATOL

    ref, top = ref
    err = max(float(np.abs(c.logits - r).max()) for c, r in zip(comps, ref))
    print(f"smoke: largest BMA log-prob error against the reference "
          f"{err:.5f} (tolerance {LOGP_ATOL}); largest |logit| {top:.3f} "
          f"(bound {LOGIT_BOUND})")
    if top >= LOGIT_BOUND:
        raise AssertionError(f"|logit| {top} outside the tolerance's bound")
    if err > LOGP_ATOL:
        raise AssertionError(f"BMA log-probs off by {err} > {LOGP_ATOL}")
    return err


def one_chip(cfg, seed: int):
    import jax

    model, state = sampling_phase(cfg, seed)
    bank = state.params
    del state
    _, reqs, comps = serve(model, bank, seed)
    check_logits(comps, reference_logp(model, bank, reqs, comps))
    print(f"smoke: peak_bytes_in_use {peak_gib(jax.devices()[0]):.3f} GiB")


def chain_devices(tree) -> dict:
    """``{chain index: device}`` of a chain-stacked pytree; every leaf must
    place its chains alike, one chain on each device."""
    import jax

    seen = None
    for leaf in jax.tree_util.tree_leaves(tree):
        where = {}
        for d, idx in leaf.sharding.devices_indices_map(leaf.shape).items():
            span = range(leaf.shape[0])[idx[0]]
            if len(span) != 1:
                raise AssertionError(f"{d} holds chains {list(span)}")
            where[span[0]] = d
        if seen is not None and where != seen:
            raise AssertionError("leaves place chains differently")
        seen = where
    if len(set(seen.values())) != len(seen):
        raise AssertionError(f"chains share a device: {seen}")
    return seen


def four_chips(cfg, seed: int):
    """4 chains sharded one per chip against each chain run alone on one
    device from the same init state, then sharded serving of the bank
    against the single-device reference."""
    import jax
    import numpy as np

    from repro.cluster import ClusterEngine
    from repro.cluster.ensemble import init_ensemble
    from repro.launch.mesh import make_data_mesh

    chains = 4
    mesh = make_data_mesh(chains)
    model, engine = sampling_engine(cfg, chains, mesh)
    require_kernel(engine.lower_chunk(
        *chunk_shapes(cfg, engine, chains, mesh)).compile(),
        "sharded sampling chunk")
    host0, keys, state, losses, scheds, batches = run_sampling(engine, cfg,
                                                               seed)
    placed = chain_devices(state.params)
    print(f"smoke: chain placement {[str(placed[c]) for c in range(chains)]}")
    bank = state.params
    del state
    for d in jax.devices()[:chains]:
        print(f"smoke: {d} peak_bytes_in_use {peak_gib(d):.3f} GiB")

    eng, reqs, comps = serve(model, bank, seed, mesh=mesh)
    chain_devices(eng.params)
    check_logits(comps, reference_logp(model, bank, reqs, comps))
    sharded = jax.device_get(bank)
    del bank, eng

    solo = ClusterEngine(engine.sampler, num_chains=1, chunk_size=COMMITS,
                         per_chain_batches=True, collect_aux=True)
    params = jax.device_put(host0, jax.devices()[0])
    for c in range(chains):
        st = init_ensemble(engine.sampler, params, keys=keys[c:c + 1])
        st, aux = solo.run(st, steps=CHUNKS * COMMITS, schedule=[scheds[c]],
                           batches=jax.tree_util.tree_map(
                               lambda b: b[:, c:c + 1], batches))
        diff = [float(np.mean(np.asarray(a[0]) != b[c])) for a, b in zip(
            jax.tree_util.tree_leaves(st.params),
            jax.tree_util.tree_leaves(sharded))]
        loss_err = float(np.abs(np.asarray(aux["loss"])[:, 0]
                                - losses[:, c]).max())
        print(f"smoke: chain {c} sharded vs alone: largest fraction of a "
              f"leaf's elements that differ {max(diff):.6f}, largest loss "
              f"difference {loss_err:.3g}")
        if max(diff) > MAX_DIFF_FRACTION:
            raise AssertionError(f"chain {c} differs from its solo run")
        del st


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    d0 = devices[0]
    print(f"platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devices)}")
    if d0.platform != "tpu":
        print("no TPU found", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices",
              file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.utils import enable_compile_cache

    print(f"smoke: compile cache {enable_compile_cache()}")
    cfg = smoke_config()
    if args.chips == 1:
        one_chip(cfg, args.seed)
    else:
        four_chips(cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
