"""End-to-end driver: train a ~100M-parameter LM with async-SGLD.

    PYTHONPATH=src python examples/train_lm.py --steps 300 --mode pipeline

A GPT-small-scale decoder (12L, d=768, 32k vocab ~ 110M params) trained on
the synthetic token stream for a few hundred steps on CPU, with periodic
checkpointing and a final decode sanity check.  Modes: sync (paper baseline)
/ consistent / inconsistent / pipeline (the beyond-paper overlapped mode).
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs.base import ArchConfig, ShapeConfig
from repro import samplers
from repro.core import WorkerModel, simulate_async
from repro.data import make_batch
from repro.models.transformer import Model, init_params
from repro.train import Engine, checkpoint_hook, make_grad_fn

LM_100M = ArchConfig(
    name="lm-100m",
    family="dense",
    source="GPT-small scale (example driver)",
    num_layers=12,
    d_model=768,
    num_heads=12,
    num_kv_heads=4,
    d_ff=2048,
    vocab_size=32_000,
    dtype="float32",
    block_pattern=("attn_mlp",),
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mode", default="consistent",
                    choices=["sync", "consistent", "inconsistent", "pipeline"])
    ap.add_argument("--tau", type=int, default=4)
    ap.add_argument("--gamma", type=float, default=3e-4)
    ap.add_argument("--sigma", type=float, default=1e-8)
    ap.add_argument("--ckpt", default="/tmp/lm100m.npz")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--chunk", type=int, default=10,
                    help="steps per jitted scan chunk")
    args = ap.parse_args()

    cfg = LM_100M
    shape = ShapeConfig("lm", seq_len=args.seq, global_batch=args.batch,
                        kind="train")
    model = Model(cfg, mesh=None)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    n = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    print(f"{cfg.name}: {n/1e6:.1f}M params, mode={args.mode}, "
          f"tokens/step={args.batch * args.seq}")

    sampler = samplers.sgld(
        args.mode, make_grad_fn(model), has_aux=True, gamma=args.gamma,
        sigma=args.sigma,
        tau=args.tau if args.mode in ("consistent", "inconsistent") else 0)
    key, init_key = jax.random.split(key)
    state = sampler.init(params, init_key)

    delays = None
    if args.mode in ("consistent", "inconsistent"):
        tr = simulate_async(WorkerModel(num_workers=8, seed=0), args.steps,
                            seed=0)
        delays = np.minimum(tr.delays, args.tau)
        print(f"delay trace: mean {tr.mean_delay:.1f} max {tr.max_delay}")

    t0 = time.time()

    last_log = [-args.log_every]

    def tok_log(step_end, _state, aux):
        if step_end - last_log[0] < args.log_every and step_end != args.steps:
            return
        last_log[0] = step_end
        loss = float(np.asarray(aux["loss"])[-1])
        tps = args.batch * args.seq * step_end / (time.time() - t0)
        print(f"step {step_end - 1:4d}  loss {loss:7.4f}  "
              f"{tps:,.0f} tok/s  ({time.time()-t0:5.1f}s)", flush=True)

    hooks = [tok_log]
    if args.ckpt:
        hooks.append(checkpoint_hook(args.ckpt, every=100))
    engine = Engine(sampler, batch_fn=lambda k: make_batch(cfg, shape, k, "train"),
                    chunk_size=args.chunk, hooks=hooks)
    state, metrics = engine.run(state, steps=args.steps, delays=delays, key=key)
    losses = np.asarray(metrics["loss"])

    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"loss {first:.3f} -> {last:.3f} "
          f"({'improved' if last < first else 'NOT improved'})")
    if args.ckpt:
        save_checkpoint(args.ckpt, state.params, step=args.steps)
        print("checkpoint:", args.ckpt)

    # decode sanity check
    cache = model.init_cache(1, 16)
    tok = jnp.zeros((1, 1), jnp.int32)
    sampled = []
    for t in range(8):
        logits, cache = jax.jit(model.serve_step)(state.params, cache, tok,
                                                  jnp.int32(t))
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        sampled.append(int(tok[0, 0]))
    print("greedy decode:", sampled)


if __name__ == "__main__":
    main()
