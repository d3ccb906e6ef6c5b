"""CLI launcher: train any assigned architecture with async-SGLD.

Real-hardware entry point (and CPU-reduced driver with --reduced):

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \
        --steps 50 --mode pipeline --batch 8 --seq 128

The sampler is the ``repro.samplers.sgld`` preset for --mode, and training
runs through the unified scan-chunked Engine: one jitted dispatch per
--chunk steps, delays fed as device arrays (no per-delay retraces), and
--fused commits through the Pallas fused Langevin kernel.  Without
--reduced the arch runs at its published widths on one device.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import samplers
from repro.configs import ShapeConfig, get_arch, get_reduced
from repro.core import WorkerModel, simulate_async
from repro.data import make_batch
from repro.models.transformer import Model, init_params
from repro.train.engine import Engine, checkpoint_hook, log_hook
from repro.train.loop import make_grad_fn
from repro.utils import enable_compile_cache


def main(argv=None):
    """Parse ``argv`` (``sys.argv`` when None), train, and return the
    final sampler state."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-scale smoke variant of the arch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mode", default="sync",
                    choices=["sync", "consistent", "inconsistent", "pipeline"])
    ap.add_argument("--tau", type=int, default=4)
    ap.add_argument("--workers", type=int, default=8,
                    help="virtual workers for the delay trace")
    ap.add_argument("--gamma", type=float, default=1e-3)
    ap.add_argument("--sigma", type=float, default=1e-5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=10,
                    help="steps per jitted scan chunk")
    ap.add_argument("--fused", action="store_true",
                    help="commit through the Pallas fused Langevin kernel")
    ap.add_argument("--save", default=None, help="checkpoint path")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_arch(args.arch)
    shape = ShapeConfig("cli", seq_len=args.seq, global_batch=args.batch,
                        kind="train")
    model = Model(cfg, mesh=None)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    print(f"{cfg.name}: {n_params/1e6:.1f}M params, mode={args.mode}"
          f"{' (fused)' if args.fused else ''}, chunk={args.chunk}")

    stale = args.mode in ("consistent", "inconsistent")
    sampler = samplers.sgld(args.mode, make_grad_fn(model), has_aux=True,
                            gamma=args.gamma, sigma=args.sigma,
                            tau=args.tau if stale else 0, fused=args.fused)
    key, init_key = jax.random.split(key)
    state = sampler.init(params, init_key)

    delays = None
    if stale:
        trace = simulate_async(WorkerModel(num_workers=args.workers,
                                           seed=args.seed), args.steps,
                               seed=args.seed)
        delays = np.minimum(trace.delays, args.tau)

    hooks = [log_hook(every=10)]
    if args.save:
        hooks.append(checkpoint_hook(args.save, every=max(args.chunk, 100)))
    engine = Engine(sampler, batch_fn=lambda k: make_batch(cfg, shape, k, "train"),
                    chunk_size=args.chunk, hooks=hooks)
    state, _ = engine.run(state, steps=args.steps, delays=delays, key=key)

    if args.save:
        from repro.checkpoint import save_checkpoint
        save_checkpoint(args.save, state.params, step=args.steps)
        print("saved", args.save)
    return state


if __name__ == "__main__":
    main()
