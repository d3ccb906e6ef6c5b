"""Unified training driver: one jitted, scan-chunked engine for every host loop.

Replaces the three hand-rolled per-step Python loops (``train/loop.py``,
``launch/train.py``, the examples) with a single ``Engine``:

- the inner loop is ``lax.scan`` over a chunk of pre-generated (batch, delay)
  pairs, jitted once with ``donate_argnums`` so the sampler state is updated
  in place — one dispatch per *chunk* instead of one per step;
- delays enter as device ``int32`` arrays, so distinct delay values never
  retrace (``engine.num_traces`` stays at the number of distinct chunk
  lengths — asserted in tests);
- host-side concerns (logging, checkpointing, metric collection) are
  pluggable hooks that run between chunks.

    engine = Engine(sampler, batch_fn=..., hooks=[log_hook(every=10)])
    state, metrics = engine.run(state, steps=1000, delays=trace.delays)
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.instrument import counters as _counters
from repro.obs.metrics import registry as _registry
from repro.obs.trace import span as _span
from repro.samplers.base import Sampler, SamplerState

PyTree = Any
BatchFn = Callable[[jax.Array], PyTree]  # key -> one batch (pure jax)
#: hook(step_end, state, chunk_aux) -> None; chunk_aux is the stacked aux
#: pytree for the chunk just finished (device arrays; index [-1] is newest).
Hook = Callable[[int, SamplerState, Any], None]


def log_hook(every: int = 10, log_fn: Callable[[str], None] = print,
             key: str = "loss") -> Hook:
    """Print ``key`` from the newest aux every ``every`` steps (chunk-aligned).

    Every line also lands in the :mod:`repro.obs.metrics` registry — a
    ``train.log_lines`` counter and a ``train.last_<key>`` gauge holding the
    newest logged scalar — so dashboards read the same value the console
    shows.  The printed format is unchanged (and pinned by tests).
    """
    import time

    reg = _registry()
    lines = reg.counter("train.log_lines", "log_hook lines emitted")
    newest = reg.gauge(f"train.last_{key}", "newest logged aux scalar")
    t0 = time.time()
    last = [-every]

    def hook(step_end: int, _state: SamplerState, aux) -> None:
        if aux is None or step_end - last[0] < every:
            return
        if isinstance(aux, dict) and key not in aux:
            return  # e.g. only threaded commit times, nothing to log
        last[0] = step_end
        val = aux[key] if isinstance(aux, dict) else aux
        leaf = jax.tree_util.tree_leaves(val)
        if not leaf:
            return
        scalar = float(np.asarray(leaf[0])[-1])
        lines.inc()
        newest.set(scalar)
        log_fn(f"step {step_end - 1:5d} {key} {scalar:8.4f} "
               f"({time.time() - t0:6.1f}s)")

    return hook


def checkpoint_hook(path: str, every: int = 100) -> Hook:
    """Save ``state.params`` to ``path`` every ``every`` steps.

    The returned hook carries a ``flush`` attribute the engine calls after
    the last chunk, so the final state is saved even when ``steps`` is not a
    multiple of ``every``.
    """
    from repro.checkpoint import save_checkpoint

    last = [0]

    def hook(step_end: int, state: SamplerState, _aux) -> None:
        if step_end - last[0] < every:
            return
        last[0] = step_end
        save_checkpoint(path, state.params, step=step_end)

    def flush(step_end: int, state: SamplerState) -> None:
        if step_end > last[0]:
            last[0] = step_end
            save_checkpoint(path, state.params, step=step_end)

    hook.flush = flush
    return hook


def merge_host_aux(aux, host_rows: dict):
    """Thread chunk-aligned host-side arrays (commit times, cumulative grad
    evals, ...) into the chunk's aux dict (shared by Engine and
    ClusterEngine)."""
    if aux is None:
        return dict(host_rows)
    if isinstance(aux, dict):
        return {**aux, **host_rows}
    return {"aux": aux, **host_rows}


def flush_hooks(hooks: Sequence[Hook], step_end: int,
                state: SamplerState) -> None:
    """After the final chunk, give every hook with a ``flush`` attribute a
    chance to act on the terminal state (e.g. save the last checkpoint)."""
    for hook in hooks:
        flush = getattr(hook, "flush", None)
        if flush is not None:
            flush(step_end, state)


def drive_chunks(run_chunk, state: SamplerState, *, steps: int,
                 chunk_size: int, hooks: Sequence[Hook], collect_aux: bool,
                 extra, batches: Optional[PyTree] = None,
                 gen_batches=None, key: Optional[jax.Array] = None,
                 commit_times=None, host_aux: Optional[dict] = None,
                 slice_batches: bool = True, chunk_info=None,
                 chunk_post=None):
    """The host chunk loop shared by :class:`Engine` and
    :class:`~repro.cluster.executor.ClusterEngine`.

    ``run_chunk(state, batches, extra, *static) -> (state, aux)`` is the
    jitted scan; ``extra`` is the per-step device input (array or pytree of
    arrays with leading axis ``steps``) sliced alongside the batches
    (delays for Engine, read versions / batch plans for ClusterEngine).
    Provide stacked ``batches`` or ``gen_batches(key, n) -> (key,
    chunk_batches)`` plus ``key``; ``slice_batches=False`` hands ``batches``
    to every chunk whole (a data *stream* the scan body indexes itself, as
    the heterogeneous-batch executor does).  ``commit_times`` (host, leading
    axis ``steps``) and any ``host_aux`` arrays are sliced per chunk and
    merged into its aux; ``chunk_info(done, n)`` may return extra *static*
    args for ``run_chunk`` (e.g. the chunk's padded bucket width).  Hooks
    run between chunks and are flushed at the end.  ``chunk_post(done,
    state) -> state`` (optional) runs *after* the chunk's hooks and may
    replace the carry — the seam the cluster executor uses for chain
    respawn and periodic fault-tolerant checkpoints; hooks therefore see
    each chunk's raw outcome (quarantines included) before it heals.
    """
    if batches is None and gen_batches is None:
        batches = jnp.zeros((steps, 1))  # batchless oracles (potentials)
    if batches is None and key is None:
        raise ValueError("generating batches from batch_fn needs `key`")
    if batches is not None and slice_batches:
        n_batches = jax.tree_util.tree_leaves(batches)[0].shape[0]
        if n_batches < steps:  # dynamic_slice would silently clamp+reuse
            raise ValueError(f"batches has {n_batches} entries, need {steps}")
    host_rows = dict(host_aux or {})
    if commit_times is not None:
        host_rows["commit_time"] = commit_times

    aux_chunks = []
    done = 0
    while done < steps:
        n = min(chunk_size, steps - done)
        # host-side spans (null ctx when tracing is disabled).  The chunk
        # span covers the host's work for one chunk: ``engine.dispatch``
        # slices the batches and ``extra`` and enqueues the jitted chunk,
        # which returns before the device finishes; ``engine.hooks`` runs
        # the hooks and ``chunk_post``, which wait on the device only where
        # they pull values to the host
        with _span("engine.chunk", start=done, size=n):
            with _span("engine.dispatch"):
                if batches is None:
                    key, chunk_batches = gen_batches(key, n)
                elif slice_batches:
                    chunk_batches = jax.tree_util.tree_map(
                        lambda x: jax.lax.dynamic_slice_in_dim(x, done, n),
                        batches)
                else:
                    chunk_batches = batches
                chunk_extra = jax.tree_util.tree_map(
                    lambda x: jax.lax.dynamic_slice_in_dim(x, done, n),
                    extra)
                static = chunk_info(done, n) if chunk_info is not None else ()
                state, aux = run_chunk(state, chunk_batches, chunk_extra,
                                       *static)
            done += n
            if host_rows:
                aux = merge_host_aux(aux, {k: np.asarray(v[done - n:done])
                                           for k, v in host_rows.items()})
            if collect_aux:
                aux_chunks.append(aux)
            with _span("engine.hooks"):
                for hook in hooks:
                    hook(done, state, aux)
                if chunk_post is not None:
                    state = chunk_post(done, state)
    flush_hooks(hooks, done, state)

    if not aux_chunks:
        return state, None
    # the host waits on the device here: the aux of every chunk is copied
    with _span("engine.fetch"):
        stacked = jax.tree_util.tree_map(
            lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0),
            *aux_chunks)
    return state, stacked


@dataclass
class Engine:
    """Scan-chunked SGLD training driver over a composable sampler.

    ``batch_fn(key) -> batch`` must be pure-jax (it is vmapped over a chunk
    of keys on device); pass ``batches=`` to ``run`` instead for
    pre-generated data.  ``chunk_size`` trades host control granularity
    (hooks, logging) against dispatch overhead.

    Transform state (the SVRG anchor, the SGHMC momentum buffer, delay
    rings) lives in ``state.inner`` and is threaded through the scanned,
    donated carry — so chunk boundaries are invisible to the samplers:
    an anchor refresh scheduled mid-chunk or across a boundary produces
    bit-identical trajectories either way (pinned by ``tests/test_zoo.py``).
    """

    sampler: Sampler
    batch_fn: Optional[BatchFn] = None
    chunk_size: int = 50
    hooks: Sequence[Hook] = ()
    donate: bool = True
    collect_aux: bool = True

    def __post_init__(self):
        self._counters = _counters("Engine")
        if self.chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {self.chunk_size}")
        donate = (0,) if self.donate else ()
        self._run_chunk = jax.jit(self._chunk_body, donate_argnums=donate)
        self._make_batches = (jax.jit(jax.vmap(self.batch_fn))
                              if self.batch_fn is not None else None)

    @property
    def num_traces(self) -> int:
        """Jit traces so far (one per distinct chunk length) — a thin view
        over the engine's :mod:`repro.analysis.instrument` counters."""
        return self._counters.traces

    # -- jitted chunk ---------------------------------------------------------
    def _chunk_body(self, state: SamplerState, batches, delays):
        # python side effect: runs once per trace, never per call
        self._counters.trace("chunk")

        def body(s, inp):
            batch, d = inp
            s, aux = self.sampler.step(s, batch, d)
            return s, (aux if self.collect_aux else None)

        return jax.lax.scan(body, state, (batches, delays))

    # -- host driver ----------------------------------------------------------
    def run(self, state: SamplerState, *, steps: int,
            batches: Optional[PyTree] = None,
            delays: Optional[np.ndarray] = None,
            key: Optional[jax.Array] = None):
        """Advance ``steps`` commits.  Returns ``(state, aux)`` where aux is
        the per-step aux pytree stacked over all steps (or ``None``).

        Provide either stacked ``batches`` (leading axis ``steps``) or a
        ``batch_fn`` at construction plus ``key`` here to generate each
        chunk's batches on device.  ``delays`` may also be a
        :class:`~repro.core.delay_model.DelayTrace`; its ``commit_times``
        are then threaded into the hook/return aux under ``"commit_time"``
        so wall-clock-axis plots need no side channel.
        """
        from repro.core.delay import validate_staleness
        from repro.core.delay_model import DelayTrace

        commit_times = None
        if isinstance(delays, DelayTrace):
            commit_times = delays.commit_times
            delays = delays.delays
        delays = (jnp.zeros((steps,), jnp.int32) if delays is None
                  else jnp.asarray(delays, jnp.int32))
        if delays.shape[0] < steps:
            raise ValueError(f"delays has {delays.shape[0]} entries, "
                             f"need {steps}")
        validate_staleness(int(np.max(np.asarray(delays[:steps]), initial=0)),
                           state.inner, context="trace")

        def gen_batches(key, n):
            key, sub = jax.random.split(key)
            return key, self._make_batches(jax.random.split(sub, n))

        return drive_chunks(
            self._run_chunk, state, steps=steps, chunk_size=self.chunk_size,
            hooks=self.hooks, collect_aux=self.collect_aux, extra=delays,
            batches=batches,
            gen_batches=gen_batches if self._make_batches is not None else None,
            key=key, commit_times=commit_times)
