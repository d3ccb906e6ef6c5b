"""Readings that set a sampling cell's correctness limits, many seeds in one
process.

    python3 benchmarks/chip/calibrate.py --workload qwen3-4b.sample \\
        --seeds 11,12,13 [--faults 3] [--out chiprun_out/cal.jsonl]

For each seed it drives the cell's engine through its first chunk of
commits (no window), frees it, and reads the numbers ``run.py`` compares:

- ``program``: the timed path against the reference, as a run reads them.

On the first ``--faults`` seeds it also reads, each against the same
reference:

- ``control``: the reference itself computed with float8 matmul operands
  (the precision below the configuration's bfloat16) in the program's
  place;
- ``half_batch``: the reference fed the first half of each batch, the mean
  taken over the rest;
- ``zero_grad``: the reference committing its noise with no gradient step;
- ``fresh_read``: the reference reading the newest iterate in place of the
  stale one the schedule names.

Every reading is judged at the cell's limits, as a run would be, and
printed with ``correct``: one JSON line per seed, on standard output and
appended to ``--out``.  Limits are set from these readings, as ``PERF.md``
records; this script changes nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import harness  # noqa: E402

KEYS = ("loss_gap", "change_gap")


def judged(nums: dict, limits: dict, commits: int) -> dict:
    """A reading with the verdict a run would give it."""
    out = harness.Outcome(e2e={}, compared={k: (nums[k], limits[k])
                                            for k in limits},
                          attempted=commits, failed=0, memory_peak_bytes=0,
                          chips=1)
    return dict({k: nums[k] for k in KEYS}, correct=out.correct)


def half(batch):
    """The first half of a batch's sequences."""
    return batch[:batch.shape[0] // 2]


def sample_seed(drv, ctx, limits: dict, faults: bool) -> dict:
    import numpy as np

    job = drv.Sampling(ctx.cfg, ctx.traffic, ctx.seed)
    first, _ = job.first_chunk()
    after, batches, delays = job.after, job.batches, job.delays()
    del job
    harness.free_device_memory()
    args = (ctx.conf, ctx.cfg, ctx.traffic, ctx.seed, batches)
    ref = drv.reference_run(*args, delays)
    losses, params = drv.program_reading(first, after)
    prog = drv.compare(ctx.cfg, ctx.seed, losses, params, ref)
    k = len(losses)
    out = {"program": judged(prog, limits, k), "losses": prog["losses"],
           "ref_losses": prog["ref_losses"],
           "leaves_left_out": prog["leaves_left_out"],
           "grad_norms": ref.grad_norms}
    if not faults:
        return out
    planted = {
        "control": drv.reference_run(*args, delays, prec="fp8"),
        "half_batch": drv.reference_run(*args, delays, view=half),
        "zero_grad": drv.reference_run(*args, delays, zero_grad=True),
        "fresh_read": drv.reference_run(*args, np.zeros_like(delays)),
    }
    for name, other in planted.items():
        nums = drv.compare(ctx.cfg, ctx.seed, other.losses, other.final, ref)
        out[name] = judged(nums, limits, k)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", type=int, default=3,
                    help="read the control and the faults on this many of "
                    "the first seeds")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    man = harness.manifest()
    cell = harness.cell(man, args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    import jax

    conf = harness.config_file(man, cell["config"])
    traffic = harness.traffic_file(cell["traffic"])
    drv = harness.driver(traffic)
    limits = traffic["limits"][conf["name"]]
    print(f"platform={jax.devices()[0].platform} "
          f"device_kind={jax.devices()[0].device_kind}")
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        ctx = SimpleNamespace(conf=conf, cfg=harness.arch_config(conf),
                              traffic=traffic, seed=seed,
                              devices=jax.devices()[:cell["chips"]])
        t = time.perf_counter()
        out = sample_seed(drv, ctx, limits, i < args.faults)
        out.update(workload=args.workload, seed=seed, limits=limits,
                   seconds=time.perf_counter() - t)
        line = json.dumps(out)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        harness.free_device_memory()
    return 0


if __name__ == "__main__":
    sys.exit(main())
