"""Batched serving driver: prefill a batch of prompts, then decode.

    PYTHONPATH=src python examples/serve_batch.py [--arch qwen3-4b] [--tokens 16]

Posterior-sampled weights (a few async-SGLD steps) -> prefill the prompt
batch through the parallel forward -> greedy-decode ``--tokens`` steps
through the ring KV cache, reporting per-step decode latency.  Uses the
reduced config of any assigned architecture.
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro import samplers
from repro.configs import ShapeConfig, get_reduced
from repro.data import make_batch
from repro.models.transformer import Model, init_params
from repro.train import Engine, make_grad_fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--warm-steps", type=int, default=5)
    args = ap.parse_args()

    cfg = get_reduced(args.arch)
    if cfg.block_pattern[0] not in ("attn_mlp", "attn_moe"):
        raise SystemExit(f"{args.arch}: prefill->cache path is attention-only; "
                         "recurrent archs serve via init_cache + replay")
    model = Model(cfg, mesh=None)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)

    # a few SGLD steps so the served weights are a posterior sample
    shape = ShapeConfig("warm", seq_len=64, global_batch=2, kind="train")
    sampler = samplers.sgld("pipeline", make_grad_fn(model), has_aux=True,
                            gamma=1e-3, sigma=1e-8)
    if args.warm_steps > 0:
        key, init_key = jax.random.split(key)
        state = sampler.init(params, init_key)
        engine = Engine(sampler,
                        batch_fn=lambda k: make_batch(cfg, shape, k, "train"),
                        chunk_size=args.warm_steps)
        state, _ = engine.run(state, steps=args.warm_steps, key=key)
        params = state.params

    # prefill
    key, pk = jax.random.split(key)
    prompts = jax.random.randint(pk, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    jprefill = jax.jit(model.prefill)
    t0 = time.time()
    logits, cache = jprefill(params, {"tokens": prompts})
    jax.block_until_ready(logits)
    print(f"prefill {args.batch}x{args.prompt_len}: {time.time()-t0:.3f}s")

    # the prefill cache covers prompt positions; extend into a decode cache
    max_seq = args.prompt_len + args.tokens
    dcache = model.init_cache(args.batch, max_seq, prefill_len=args.prompt_len)
    dcache["attn"]["k"] = dcache["attn"]["k"].at[:, :, :args.prompt_len].set(
        cache["attn"]["k"])
    dcache["attn"]["v"] = dcache["attn"]["v"].at[:, :, :args.prompt_len].set(
        cache["attn"]["v"])

    jserve = jax.jit(model.serve_step)
    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    outs = [tok]
    lat = []
    for t in range(args.tokens):
        t0 = time.time()
        logits, dcache = jserve(params, dcache, tok,
                                jnp.int32(args.prompt_len + t))
        jax.block_until_ready(logits)
        lat.append(time.time() - t0)
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        outs.append(tok)
    gen = jnp.concatenate(outs, axis=1)
    lat_ms = [round(x * 1e3, 1) for x in lat]
    print(f"decode: median {sorted(lat_ms)[len(lat_ms)//2]}ms/token "
          f"(first {lat_ms[0]}ms incl. compile)")
    for b in range(args.batch):
        print(f"  seq{b}: {[int(x) for x in gen[b][:10]]}...")


if __name__ == "__main__":
    main()
