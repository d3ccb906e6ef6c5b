"""Delayed-gradient sampling through ``ClusterEngine``.

Set-up makes the weights and a pool of token batches on the device from
the seed, builds one W-Con sampler with the fused update and one engine,
and drives it through its first chunk of commits, which compiles it.  That
chunk's losses and the parameters after it are what the reference checks.
The window then calls ``engine.run`` on the same state, a few chunks a
call, until ``--seconds`` have passed.  ``sample_tokens_per_s`` is every
committed token over the whole window, per chip.

The check, after the window and once the program's state is freed,
follows the same commits in the plain reference (float32, ``highest``),
from the same weights, batches, staleness and noise stream, and compares
each commit's loss and each leaf's norm of change over the chunk.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import reference, traces
from chipbench.harness import Outcome, base_key, free_device_memory, \
    memory_peak
from chipbench.weights import make_tokens, make_weights

POOL_TAG = 0x504F  # fold_in tags of the run's keys
CHAIN_TAG = 0x4348


def build(cfg, tr: dict):
    """``(model, engine)``: W-Con SGLD with the fused update, ``chains``
    chains on explicit per-chain batches, aux (the loss) collected."""
    from repro import samplers
    from repro.cluster import ClusterEngine
    from repro.models.transformer import Model
    from repro.train.loop import make_grad_fn

    model = Model(cfg)
    sampler = samplers.sgld("consistent", make_grad_fn(model), has_aux=True,
                            tau=tr["tau"], fused=True, gamma=tr["gamma"],
                            sigma=tr["sigma"])
    engine = ClusterEngine(sampler, num_chains=tr["chains"],
                           chunk_size=tr["commits_per_chunk"],
                           per_chain_batches=True, collect_aux=True)
    return model, engine


def schedules(tr: dict, steps: int, seed: int):
    """The first ``ensemble_async`` schedule set from ``seed`` whose every
    chain's staleness reaches ``tau`` and stays within it."""
    from repro.cluster import ensemble_async
    from repro.core import WorkerModel

    tau = tr["tau"]
    for s in range(seed % 2**31, seed % 2**31 + 100):
        sched = ensemble_async(WorkerModel(num_workers=tau, seed=s), steps,
                               tr["chains"], seed=s)
        if all(x.max_delay == tau for x in sched):
            return sched
    raise RuntimeError(f"no schedule reaching tau={tau} from seed {seed}")


def chain_key(seed: int):
    import jax

    return jax.random.fold_in(base_key(seed), CHAIN_TAG)


class Sampling:
    """One engine and its state, driven through the first chunk."""

    def __init__(self, cfg, tr: dict, seed: int):
        import jax

        self.tr = tr
        self.k = tr["commits_per_chunk"]
        self.m = self.k * tr["chunks_per_call"]
        rows = (tr["pool_commits"], tr["chains"], tr["sequences_per_commit"],
                tr["seq_len"] + 1)
        self.pool = make_tokens(seed, rows, cfg.vocab_size, POOL_TAG)
        self.take = {n: jax.jit(lambda p, i, n=n: {
            "tokens": jax.lax.dynamic_slice_in_dim(p, i, n)})
            for n in (self.k, self.m)}
        self.first_sched = schedules(tr, self.k, seed)
        self.call_sched = schedules(tr, self.m, seed + 1)
        _, self.engine = build(cfg, tr)
        params = make_weights(cfg, seed)
        self.state = self.engine.init(params, chain_key(seed))
        del params
        self.offset = self.k

    def first_chunk(self):
        """Run the first chunk; returns ``(losses (K, C), seconds spent
        copying the check's inputs to the host)``."""
        import jax

        batches = self.take[self.k](self.pool, 0)
        self.state, aux = self.engine.run(
            self.state, steps=self.k, schedule=self.first_sched,
            batches=batches)
        t = time.perf_counter()
        self.after = jax.device_get(self.state.params)
        self.batches = np.asarray(batches["tokens"])
        return np.asarray(aux["loss"]), time.perf_counter() - t

    def call(self):
        """One window call: ``chunks_per_call`` chunks on fresh rows."""
        n = self.tr["pool_commits"]
        if self.offset + self.m > n:
            self.offset = 0
        batches = self.take[self.m](self.pool, self.offset)
        self.offset += self.m
        self.state, aux = self.engine.run(
            self.state, steps=self.m, schedule=self.call_sched,
            batches=batches)
        return np.asarray(aux["loss"])

    def delays(self) -> np.ndarray:
        return np.stack([s.delays for s in self.first_sched], axis=1)


def reference_run(conf: dict, cfg, tr: dict, seed: int, batches, delays,
                  prec: str = "highest", view=None, zero_grad: bool = False):
    """Chain 0's first chunk of commits in the plain reference, from the
    same weights, batches, staleness and noise stream as the program.
    ``prec`` "fp8" is the control; ``view`` (a cut of each batch), zero
    ``delays`` and ``zero_grad`` plant faults in the reference put in the
    program's place."""
    import jax

    rows = batches[:, 0]
    if view is not None:
        rows = np.stack([view(b) for b in rows])
    key = jax.random.split(chain_key(seed), tr["chains"])[0]
    x0 = make_weights(cfg, seed)
    return reference.sgld_commits(
        reference.RefConfig.from_file(conf), prec, x0, rows, delays[:, 0],
        key, tr["gamma"], tr["sigma"], depth=tr["tau"] + 1,
        zero_grad=zero_grad)


def compare(cfg, seed: int, losses, after, ref) -> dict:
    """The numbers compared for chain 0: the largest gap between the
    program's and the reference's loss over the first chunk's commits
    (``losses`` (K,)), and the worst leaf's gap in norm of change over the
    chunk (``after``: chain 0's parameters after it, on the host)."""
    import jax

    x0 = jax.device_get(make_weights(cfg, seed))
    change, left_out = reference.change_gap(x0, after, ref.final,
                                            ref.grad_norms)
    losses = np.asarray(losses)
    return {"loss_gap": float(np.max(np.abs(losses - ref.losses))),
            "change_gap": float(change), "leaves_left_out": left_out,
            "losses": [float(x) for x in losses],
            "ref_losses": [float(x) for x in ref.losses]}


def program_reading(first, after):
    """Chain 0's losses and parameters from the program's first chunk."""
    import jax

    return (np.asarray(first)[:, 0],
            jax.tree_util.tree_map(lambda a: a[0], after))


def run(ctx) -> Outcome:
    import jax
    from jax.profiler import TraceAnnotation
    from repro.analysis.instrument import instrument

    tr, cfg = ctx.traffic, ctx.cfg
    marks = [("start", time.perf_counter())]
    job = Sampling(cfg, tr, ctx.seed)
    marks.append(("engine and state built", time.perf_counter()))
    first, capture_s = job.first_chunk()
    marks.append(("first chunk", time.perf_counter()))
    job.call()  # every host-side program of a window call, compiled here
    marks.append(("warm call", time.perf_counter()))
    tokens_per_commit = tr["sequences_per_commit"] * tr["seq_len"]
    with instrument() as rep, traces.recording(ctx.trace) as rec:
        with TraceAnnotation(traces.WINDOW):
            t0 = time.perf_counter()
            setup_s = t0 - ctx.t_start - capture_s
            losses = []
            while True:
                with TraceAnnotation("sample.run"):
                    losses.append(job.call())
                if time.perf_counter() - t0 >= ctx.seconds:
                    break
            window_s = time.perf_counter() - t0
    losses = np.concatenate(losses)
    commits = losses.shape[0]
    peak = memory_peak(ctx.devices)
    after, batches, delays = job.after, job.batches, job.delays()
    param_leaves = [(int(np.prod(a.shape[1:])), a.dtype.itemsize)
                    for a in jax.tree_util.tree_leaves(after)]
    del job
    free_device_memory()
    t_check = time.perf_counter()
    ref = reference_run(ctx.conf, cfg, tr, ctx.seed, batches, delays)
    nums = compare(cfg, ctx.seed, *program_reading(first, after), ref)
    check_s = time.perf_counter() - t_check
    limits = tr["limits"][ctx.conf["name"]]
    chips = len(ctx.devices)
    return Outcome(
        e2e={"sample_tokens_per_s":
             commits * tokens_per_commit / window_s / chips,
             "setup_s": setup_s},
        compared={k: (nums[k], limits[k]) for k in limits},
        attempted=int(losses.size),
        failed=int((~np.isfinite(losses)).sum()),
        memory_peak_bytes=peak, chips=chips,
        layer={"trace": rec.get("trace"), "commits": commits,
               "tokens": commits * tokens_per_commit, "window_s": window_s,
               "param_leaves": param_leaves},
        notes=[f"compiles in window: {rep.xla_compiles} "
               f"(traces {rep.num_traces})",
               f"first chunk losses {nums['losses']}, reference "
               f"{nums['ref_losses']}, leaves left out of the change: "
               f"{nums['leaves_left_out']}",
               f"window: {commits} commits in {window_s:.3f} s; check "
               f"capture {capture_s:.3f} s kept out of setup_s; reference "
               f"check {check_s:.3f} s",
               "set-up: " + ", ".join(f"{k} at {t - ctx.t_start:.2f} s"
                                      for k, t in marks)])
