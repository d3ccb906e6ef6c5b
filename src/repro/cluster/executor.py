"""ClusterEngine: the device-parallel multi-chain async-SGLD executor.

Same contract as :class:`repro.train.engine.Engine` — jitted ``lax.scan``
chunks, donated carry, host hooks between chunks, a flat retrace counter —
but the carry is a C-chain :func:`~repro.cluster.ensemble.init_ensemble`
state and each scan step advances the whole population through the vmapped
transform chain.

Delays are *endogenous*: the scan input is the schedule's per-chain
``read_versions`` and the jitted body derives staleness as
``server_version - read_version`` from the carried commit counter, so the
device executes the worker schedule instead of consuming a staleness
side-channel.  With ``mesh=`` the chunk body runs under
``jax.shard_map`` with chains split over the ``data`` axis — pure
SPMD, no cross-chain communication, so per-chain trajectories are identical
sharded or not.

Batch sizes are part of the schedule: under ``batch_policy="inverse-speed"``
(or ``"explicit"``) every commit carries its own minibatch size and data
offset, and the scan body gathers a *bucket-padded* window from the ``data``
stream — each chunk pads to the bucket-ladder rung of its largest commit,
so a mixed-size schedule compiles **one trace per rung**, never one per
size (the discipline :class:`~repro.cluster.serve.ServeEngine` applies to
query batches).  The mask (:class:`~repro.samplers.transforms.MaskedBatch`)
keeps padding rows out of the gradient average.  The default
``batch_policy="fixed"`` is the legacy fixed-shape path, bit-identical to
the pre-heterogeneous executor.

Faults are first-class: a chaos schedule's per-commit liveness mask turns a
crashed worker's in-flight commit into a masked no-op inside the same scan
(same one-trace-per-rung contract); ``health_check=True`` carries a sticky
per-chain health mask through the scan (a NaN/Inf iterate quarantines the
chain on device, no retrace) with quarantined chains respawned from healthy
donors at chunk boundaries; and ``run(checkpoint_path=...)`` +
:meth:`ClusterEngine.resume` give preemption-tolerant restarts that stitch
bitwise against an uninterrupted run.  Every fault knob is opt-in and
structural: a zero-fault configuration threads no extra scan inputs and
compiles the exact pre-fault program.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.analysis.instrument import counters as _counters
from repro.cluster.ensemble import ensemble_step, init_ensemble
from repro.cluster.schedule import (
    WorkerSchedule,
    stack_batch_info,
    stack_liveness,
    stack_schedules,
    stack_worker_info,
)
from repro.core.delay import validate_staleness
from repro.core.delay_model import BATCH_POLICIES
from repro.obs.metrics import EXPERT_LOAD_BUCKETS, STALENESS_BUCKETS, \
    registry as _registry
from repro.obs.trace import span as _span
from repro.samplers.base import Sampler, SamplerState
from repro.samplers.transforms import MaskedBatch
from repro.train.engine import Hook, drive_chunks
from repro.utils import bucket_size

PyTree = Any
BatchFn = Callable[[jax.Array], PyTree]  # key -> one chain's batch (pure jax)

#: accepted `schedule=` forms for :meth:`ClusterEngine.run`
ScheduleLike = Any  # WorkerSchedule | Sequence[WorkerSchedule] | np.ndarray | None

#: fold_in tag minting a respawned chain's fresh noise stream from the
#: quarantined chain's (frozen) key — deterministic, so a resumed run
#: respawns identically to an uninterrupted one ("RES\x01")
_RESPAWN_TAG = 0x5245_5301


class HealthState(NamedTuple):
    """Scan carry under ``health_check``: the ensemble state plus the sticky
    per-chain health mask (``True`` = healthy, flips ``False`` forever —
    until respawn — once a chain's iterate goes NaN/Inf).

    Delegating properties keep the :class:`~repro.samplers.base.SamplerState`
    surface (``params``/``step``/``key``/``inner``), so hooks, recorders and
    ``save_ensemble`` work on either carry unchanged.
    """

    state: SamplerState
    health: jax.Array  # (C,) bool

    @property
    def params(self):
        """Chain-stacked iterate (delegates to the wrapped state)."""
        return self.state.params

    @property
    def step(self):
        """Per-chain commit counters (delegates to the wrapped state)."""
        return self.state.step

    @property
    def key(self):
        """Per-chain PRNG keys (delegates to the wrapped state)."""
        return self.state.key

    @property
    def inner(self):
        """Per-transform chain state (delegates to the wrapped state)."""
        return self.state.inner


def _chain_select(keep: jax.Array, new: PyTree, old: PyTree) -> PyTree:
    """Per-chain ``jnp.where`` over chain-stacked pytrees: rows of chains
    with ``keep=False`` retain their old value (the masked no-op commit)."""
    def sel(n, o):
        mask = keep.reshape(keep.shape + (1,) * (n.ndim - 1))
        return jnp.where(mask, n, o)
    return jax.tree_util.tree_map(sel, new, old)


def _finite_chains(params: PyTree) -> jax.Array:
    """(C,) bool: which chains' iterates are all-finite (float leaves)."""
    leaves = jax.tree_util.tree_leaves(params)
    c = leaves[0].shape[0]
    ok = jnp.ones((c,), bool)
    for leaf in leaves:
        if jnp.issubdtype(leaf.dtype, jnp.inexact):
            ok &= jnp.all(jnp.isfinite(leaf.reshape(c, -1)), axis=1)
    return ok


def _poison_chains(bad: jax.Array, params: PyTree) -> PyTree:
    """NaN the float leaves of chains with ``bad=True`` (fault injection)."""
    def nanify(x):
        if not jnp.issubdtype(x.dtype, jnp.inexact):
            return x
        mask = bad.reshape(bad.shape + (1,) * (x.ndim - 1))
        return jnp.where(mask, jnp.asarray(jnp.nan, x.dtype), x)
    return jax.tree_util.tree_map(nanify, params)


@dataclass
class ClusterEngine:
    """Scan-chunked executor for a C-chain async-SGLD ensemble.

    ``batch_fn(key) -> batch`` (optional) generates an *independent*
    minibatch per (step, chain) key on device; explicit ``batches`` passed to
    :meth:`run` are broadcast to every chain unless ``per_chain_batches=True``
    (then their second axis is the chain axis).  ``mesh`` shards the chain
    axis over ``chain_axis`` (``num_chains`` must be divisible by that mesh
    axis size).

    ``batch_policy`` selects how commits consume data:

    - ``"fixed"`` (default): one fixed-shape minibatch per commit — the
      legacy contract, bit-identical to the pre-heterogeneous executor.
    - ``"inverse-speed"``: per-commit sizes come from the schedule's
      ``batch_sizes`` (compiled from a
      :meth:`WorkerModel.batch_sizes <repro.core.delay_model.WorkerModel>`
      policy: slow workers amortize staleness over large batches); commits
      consume bucket-padded masked windows of the ``data=`` stream passed to
      :meth:`run`.
    - ``"explicit"``: like inverse-speed, but sizes come from the
      ``batch_sizes=`` array passed to :meth:`run` (snapped up the
      ``buckets`` ladder).

    The sampler must use the per-example masked-oracle contract for the
    non-fixed policies (``samplers.sgld(..., base_batch=...)`` or a chain
    containing :func:`~repro.samplers.transforms.masked_gradients`).

    ``worker_rng`` derives each commit's noise key from
    ``(chain key, worker_id, worker-local slot)`` instead of the carried
    sequential split, making every worker's noise stream reproducible
    independently of commit order (see
    :func:`~repro.cluster.ensemble.worker_keys`).

    ``health_check=True`` threads a sticky per-chain health mask through the
    scan (:class:`HealthState` carry): a chain whose iterate goes NaN/Inf is
    quarantined *on device* — its subsequent commits become masked no-ops —
    and, with ``respawn=True``, is recloned from a healthy donor chain with
    a fresh ``fold_in`` noise key at the next chunk boundary.  Both default
    off; a zero-fault configuration compiles the exact pre-fault program.
    """

    sampler: Sampler
    num_chains: int
    chunk_size: int = 50
    hooks: Sequence[Hook] = ()
    donate: bool = True
    collect_aux: bool = False
    batch_fn: Optional[BatchFn] = None
    per_chain_batches: bool = False
    mesh: Any = None
    chain_axis: str = "data"
    batch_policy: str = "fixed"
    buckets: Optional[Sequence[int]] = None
    worker_rng: bool = False
    health_check: bool = False
    respawn: bool = True

    def __post_init__(self):
        self._counters = _counters("ClusterEngine")
        if self.chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {self.chunk_size}")
        if self.num_chains < 1:
            raise ValueError(f"num_chains must be >= 1, got {self.num_chains}")
        if self.batch_policy not in BATCH_POLICIES:
            raise ValueError(f"unknown batch_policy {self.batch_policy!r} "
                             f"(choose from {BATCH_POLICIES})")
        if self.batch_policy != "fixed" and self.batch_fn is not None:
            raise ValueError(
                "batch_fn generates fixed-shape minibatches; heterogeneous "
                "batch policies consume a `data=` stream passed to run()")
        if self.mesh is not None:
            n_shards = self.mesh.shape[self.chain_axis]
            if self.num_chains % n_shards:
                raise ValueError(
                    f"num_chains={self.num_chains} must be divisible by mesh "
                    f"axis {self.chain_axis!r} (size {n_shards})")
        # one jitted chunk per batch layout; only the layouts actually run
        # get traced/compiled (the counter they bump is shared)
        self._chunk_shared = self._build_chunk(batch_axis=None)
        self._chunk_per_chain = self._build_chunk(batch_axis=0)
        self._masked_chunks: dict = {}  # pad width -> jitted masked chunk
        self._make_batches = (jax.jit(jax.vmap(jax.vmap(self.batch_fn)))
                              if self.batch_fn is not None else None)
        reg = _registry()
        self._m_staleness = reg.histogram(
            "cluster.staleness", STALENESS_BUCKETS,
            "per-commit staleness tau = version - read_version")
        self._m_commits = reg.counter(
            "cluster.commits", "commits executed (steps x chains)")
        self._m_grad_evals = reg.counter(
            "cluster.grad_evals",
            "per-example gradient evaluations (non-fixed batch policies)")
        self._m_max_stale = reg.gauge(
            "cluster.max_staleness", "largest tau in the newest schedule")
        self._m_faults = reg.counter(
            "faults.injected",
            "fault events injected (lost commits + NaN poisons)")
        self._m_quarantined = reg.counter(
            "chains.quarantined",
            "chains newly quarantined by the sticky health mask")
        self._m_respawned = reg.counter(
            "chains.respawned",
            "quarantined chains respawned from a healthy donor")
        self._m_unhealthy = reg.gauge(
            "chains.unhealthy", "chains currently quarantined")
        self._m_moe_held = reg.counter(
            "moe.assignments_held",
            "(token, expert) assignments routed to the experts held here")
        self._m_moe_load = reg.histogram(
            "moe.expert_load", EXPERT_LOAD_BUCKETS,
            "tokens one held expert of one MoE layer took in one commit")

    @property
    def num_traces(self) -> int:
        """Jit traces so far (one per chunk layout / bucket rung) — a thin
        view over the engine's :mod:`repro.analysis.instrument` counters."""
        return self._counters.traces

    def _step_fn(self, batch_axis: Optional[int]):
        return ensemble_step(self.sampler, batch_axis=batch_axis,
                             worker_rng=self.worker_rng)

    def _step_args(self, s, batch, delay, ex):
        if self.worker_rng:
            return (s, batch, delay, ex["wid"], ex["slot"])
        return (s, batch, delay)

    def _advance(self, step_fn, carry, batch, ex):
        """One population commit with the fault/health guards.

        The guards are *structural*: ``"alive"``/``"poison"`` membership in
        ``ex`` and the carry's :class:`HealthState`-ness are trace-time
        facts, so a zero-fault run traces the exact pre-fault body.  The
        commit counter always advances — a masked no-op still burns its
        version slot, keeping the endogenous ``step - read_version``
        staleness aligned with the schedule's all-commit numbering.
        """
        if isinstance(carry, HealthState):
            s, health = carry.state, carry.health
        else:
            s, health = carry, None
        delay = s.step.astype(jnp.int32) - ex["rv"]  # endogenous
        s_new, aux = step_fn(*self._step_args(s, batch, delay, ex))
        if "poison" in ex:
            s_new = s_new._replace(
                params=_poison_chains(ex["poison"], s_new.params))
        keep = None
        if health is not None:
            health = health & _finite_chains(s_new.params)  # sticky flip
            keep = health
        if "alive" in ex:
            keep = ex["alive"] if keep is None else keep & ex["alive"]
        if keep is not None:
            s_new = SamplerState(
                params=_chain_select(keep, s_new.params, s.params),
                step=s_new.step,
                key=_chain_select(keep, s_new.key, s.key),
                inner=_chain_select(keep, s_new.inner, s.inner))
        out = s_new if health is None else HealthState(s_new, health)
        return out, (aux if self.collect_aux else None)

    def _build_chunk(self, batch_axis: Optional[int]):
        """Jitted scan over one chunk; ``batch_axis=0`` vmaps the batch over
        the chain axis, ``None`` broadcasts one batch to every chain."""

        def chunk(state, batches, extra):
            # python side effect: runs once per trace, never per call
            self._counters.trace(f"chunk[batch_axis={batch_axis}]")
            step_fn = self._step_fn(batch_axis)

            def body(s, inp):
                batch, ex = inp
                return self._advance(step_fn, s, batch, ex)

            return jax.lax.scan(body, state, (batches, extra))

        if self.mesh is not None:
            ax = self.chain_axis
            batch_spec = P(None, ax) if batch_axis == 0 else P()
            chunk = jax.shard_map(chunk, mesh=self.mesh,
                                  in_specs=(P(ax), batch_spec, P(None, ax)),
                                  out_specs=(P(ax), P(None, ax)),
                                  check_vma=False)
        return jax.jit(chunk, donate_argnums=(0,) if self.donate else ())

    def _build_masked_chunk(self, pad: int):
        """Jitted scan whose per-step batch is a bucket-padded masked window
        of the data stream: ``pad`` is the chunk's ladder rung (static —
        one trace per rung), ``extra`` carries per-(step, chain) data
        offsets and real sizes, and the gather wraps modulo the stream
        length so offsets never index out of bounds."""

        def chunk(state, data, extra):
            # python side effect: runs once per trace, never per call
            self._counters.trace(f"masked_chunk[pad={pad}]")
            step_fn = self._step_fn(0)
            n_data = jax.tree_util.tree_leaves(data)[0].shape[0]

            def window(off):  # () int32 -> (pad, ...) rows, wrapped
                idx = jax.lax.rem(off + jnp.arange(pad, dtype=jnp.int32),
                                  n_data)
                return jax.tree_util.tree_map(
                    lambda x: jnp.take(x, idx, axis=0), data)

            def body(s, ex):
                batch = MaskedBatch(data=jax.vmap(window)(ex["off"]),
                                    size=ex["size"])
                return self._advance(step_fn, s, batch, ex)

            return jax.lax.scan(body, state, extra)

        if self.mesh is not None:
            ax = self.chain_axis
            chunk = jax.shard_map(chunk, mesh=self.mesh,
                                  in_specs=(P(ax), P(), P(None, ax)),
                                  out_specs=(P(ax), P(None, ax)),
                                  check_vma=False)
        return jax.jit(chunk, donate_argnums=(0,) if self.donate else ())

    def _run_masked_chunk(self, state, data, extra, pad: int):
        fn = self._masked_chunks.get(pad)
        if fn is None:
            fn = self._masked_chunks[pad] = self._build_masked_chunk(pad)
        return fn(state, data, extra)

    # -- init -----------------------------------------------------------------
    def init(self, params: PyTree, key: jax.Array, *,
             jitter: float = 0.0) -> SamplerState:
        """C-chain ensemble state; chain ``c``'s key is ``split(key, C)[c]``.

        With a mesh the state is built in its sharded layout: each device
        computes only its own chains, so no device ever holds the whole
        ensemble."""
        def init(params, key):
            return init_ensemble(self.sampler, params, key,
                                 num_chains=self.num_chains, jitter=jitter)

        if self.mesh is None:
            return init(params, key)
        sharding = jax.sharding.NamedSharding(self.mesh, P(self.chain_axis))
        return jax.jit(init, out_shardings=sharding)(params, key)

    def lower_chunk(self, state: SamplerState, batches: PyTree, extra: dict):
        """Lower the chunk program :meth:`run` dispatches for per-chain
        batches (generated, or explicit with ``per_chain_batches``): one
        chunk's ``batches`` ``(n, C, ...)`` and ``extra`` (``{"rv": (n, C)
        int32}`` read versions for a fault-free schedule), as arrays or
        ``jax.ShapeDtypeStruct``\\ s.  ``.compile()`` of the result gives
        the program's ``memory_analysis()`` and its compiled text."""
        return self._chunk_per_chain.lower(state, batches, extra)

    # -- state export ---------------------------------------------------------
    def save_ensemble(self, state: SamplerState, path: str) -> None:
        """Export the chain bank: the chain-stacked params in the ensemble
        layout :func:`~repro.checkpoint.restore_ensemble` (and therefore
        :meth:`~repro.cluster.serve.ServeEngine.from_checkpoint`) restores,
        with the newest per-chain commit counter as the checkpoint step."""
        from repro.checkpoint import save_checkpoint

        save_checkpoint(path, state.params,
                        step=int(np.max(np.asarray(state.step))))

    # -- schedule normalization ------------------------------------------------
    def _compile_schedule(self, schedule: ScheduleLike, steps: int):
        """-> (extra dict of (steps, C) host arrays, commit_times | None,
        batch_info (sizes, offsets) | None).

        ``extra`` always carries ``rv`` (read versions); ``wid``/``slot``
        (worker attribution) join it under ``worker_rng``, and ``alive``
        (commit liveness) joins it only when a chaos schedule actually lost
        a commit — fault-free schedules compile the pre-fault program.
        """
        c = self.num_chains
        raw_delays = isinstance(schedule, (np.ndarray, jnp.ndarray))
        if schedule is None:
            scheds = [WorkerSchedule.sync(steps)] * c
        elif raw_delays:
            arr = np.asarray(schedule)
            if arr.ndim == 1:
                scheds = [WorkerSchedule.from_delays(arr)] * c
            elif arr.ndim == 2:
                scheds = [WorkerSchedule.from_delays(arr[:, i])
                          for i in range(arr.shape[1])]
            else:
                raise ValueError("delay array must be (steps,) or (steps, C)")
        else:
            scheds = ([schedule] * c if isinstance(schedule, WorkerSchedule)
                      else list(schedule))
        if len(scheds) != c:
            raise ValueError(f"got {len(scheds)} per-chain schedules for "
                             f"{c} chains")
        rv, times = stack_schedules(scheds, steps=steps)
        extra = {"rv": rv}
        if self.worker_rng:
            wid, slot = stack_worker_info(scheds, steps)
            extra["wid"], extra["slot"] = wid, slot
        live = stack_liveness(scheds, steps)
        if live is not None:
            extra["alive"] = live
        # synthetic schedules (sync default, raw delay arrays) carry no
        # wall-clock information; don't present arange times as simulated
        times = None if (schedule is None or raw_delays) else times
        return extra, times, stack_batch_info(scheds, steps)

    def _compile_batch_plan(self, batch_info, batch_sizes, steps: int):
        """-> ((steps, C) int32 sizes, (steps, C) int64 offsets) for the
        masked path, honoring the engine's batch policy."""
        if self.batch_policy == "explicit":
            if batch_sizes is None:
                raise ValueError(
                    'batch_policy="explicit" needs batch_sizes= '
                    "((steps,) or (steps, C)) passed to run()")
            sizes = np.asarray(batch_sizes, np.int64)
            if sizes.ndim == 0:
                sizes = np.full((steps,), int(sizes), np.int64)
            if sizes.ndim == 1:
                sizes = np.tile(sizes[:, None], (1, self.num_chains))
            if sizes.shape[0] < steps:
                raise ValueError(f"batch_sizes has {sizes.shape[0]} entries, "
                                 f"need {steps}")
            sizes = sizes[:steps]
            snap = np.vectorize(lambda b: bucket_size(int(b), self.buckets))
            sizes = snap(sizes).astype(np.int32)
            offs = np.zeros_like(sizes, dtype=np.int64)
            np.cumsum(sizes[:-1].astype(np.int64), axis=0, out=offs[1:])
            return sizes, offs
        # inverse-speed: the schedule is the plan, offsets included
        if batch_info is None:
            raise ValueError(
                'batch_policy="inverse-speed" needs schedules carrying '
                'batch_sizes (ensemble_async(..., '
                'batch_policy="inverse-speed") or '
                "WorkerSchedule.with_batch_sizes)")
        return batch_info

    # -- fault tolerance -------------------------------------------------------
    @staticmethod
    def _put_like(arr, like):
        """Device-put a host array with ``like``'s sharding (identity
        placement when ``like`` carries none)."""
        if isinstance(like, jax.Array):
            return jax.device_put(jnp.asarray(arr), like.sharding)
        return jnp.asarray(arr)

    def _as_carry(self, state):
        """Wrap ``state`` into the carry :meth:`run` scans: a
        :class:`HealthState` (all-healthy) under ``health_check``."""
        if not self.health_check or isinstance(state, HealthState):
            return state
        health = jnp.ones((self.num_chains,), bool)
        if self.mesh is not None:
            health = jax.device_put(health, jax.sharding.NamedSharding(
                self.mesh, P(self.chain_axis)))
        return HealthState(state, health)

    def _heal(self, carry: HealthState, prev_health) -> HealthState:
        """Chunk-boundary quarantine bookkeeping and (optional) respawn.

        Quarantined chains are recloned from healthy donors (round-robin):
        donor params/inner replace the sick chain's, and the sick chain's
        *frozen* key is ``fold_in``-minted into a fresh noise stream — all
        a deterministic function of the carried state, so a resumed run
        respawns identically to an uninterrupted one.
        """
        health = np.asarray(carry.health)
        sick = np.flatnonzero(~health)
        newly = int((~health & prev_health[0]).sum())
        prev_health[0] = health
        if newly:
            self._m_quarantined.inc(newly)
        self._m_unhealthy.set(float(sick.size))
        if sick.size == 0 or not self.respawn:
            return carry
        donors = np.flatnonzero(health)
        if donors.size == 0:
            return carry  # total loss — nothing healthy left to clone
        donor = donors[np.arange(sick.size) % donors.size]
        state = carry.state

        def clone(leaf):
            a = np.array(leaf)
            a[sick] = a[donor]
            return self._put_like(a, leaf)

        with _span("faults.respawn", chains=[int(i) for i in sick],
                   donors=[int(i) for i in donor]):
            keys = np.array(state.key)
            fresh = jax.vmap(
                lambda k: jax.random.fold_in(k, _RESPAWN_TAG))(
                    jnp.asarray(keys[sick]))
            keys[sick] = np.asarray(fresh)
            healed = SamplerState(
                params=jax.tree_util.tree_map(clone, state.params),
                step=state.step,  # commit counters tick in lockstep
                key=self._put_like(keys, state.key),
                inner=jax.tree_util.tree_map(clone, state.inner))
            health = self._put_like(np.ones_like(health), carry.health)
        self._m_respawned.inc(int(sick.size))
        prev_health[0] = np.asarray(health)
        return HealthState(healed, health)

    def _save_run_checkpoint(self, path: str, carry, done: int,
                             base: np.ndarray) -> None:
        from repro.checkpoint import save_checkpoint

        save_checkpoint(path, {"carry": carry, "manifest": {
            "done": np.asarray(done, np.int64),
            "base": np.asarray(base, np.int64)}}, step=int(done))

    def _load_run_checkpoint(self, path: str, state):
        from repro.checkpoint import restore_checkpoint

        template = self._as_carry(state)
        like = {"carry": template, "manifest": {
            "done": np.zeros((), np.int64),
            "base": np.zeros((self.num_chains,), np.int64)}}
        tree = restore_checkpoint(path, like)
        carry = jax.tree_util.tree_map(
            lambda t, x: self._put_like(x, t), template, tree["carry"])
        return (carry, int(tree["manifest"]["done"]),
                np.asarray(tree["manifest"]["base"]))

    # -- host driver ----------------------------------------------------------
    def run(self, state: SamplerState, *, steps: int,
            schedule: ScheduleLike = None,
            batches: Optional[PyTree] = None,
            key: Optional[jax.Array] = None,
            data: Optional[PyTree] = None,
            batch_sizes: Optional[np.ndarray] = None,
            poison: Optional[np.ndarray] = None,
            checkpoint_path: Optional[str] = None,
            checkpoint_every: Optional[int] = None):
        """Advance every chain ``steps`` commits under ``schedule``.

        ``schedule`` may be one :class:`WorkerSchedule` (broadcast), a
        sequence of C per-chain schedules, a raw delay ndarray
        (``(steps,)`` or ``(steps, C)``), or ``None`` (synchronous, tau=0).
        Returns ``(state, aux)`` with aux stacked ``(steps, C, ...)`` when
        ``collect_aux`` (plus ``commit_times`` threaded into hook aux when
        the schedule carries them).

        Under a non-fixed ``batch_policy``, ``data=`` is the shared example
        stream (pytree, leading axis = rows): commit ``k`` of chain ``c``
        consumes rows ``[offset, offset + size)`` — offsets wrap modulo the
        stream length, and restart at 0 on every :meth:`run` call — as a
        bucket-padded :class:`~repro.samplers.transforms.MaskedBatch`, and
        cumulative ``grad_evals`` are threaded into the hook aux next to
        ``commit_time``.

        Fault knobs (all opt-in, all structurally invisible when unused):

        - chaos schedules carrying an ``alive`` mask execute lost commits
          as masked no-ops (the version slot still burns);
        - ``poison`` — a ``(steps, C)`` bool mask NaN'ing chain iterates at
          chosen commits (deterministic fault injection for tests/bench);
        - ``checkpoint_path`` — write an atomic resumable checkpoint (carry
          + manifest) at every chunk boundary, or every ``checkpoint_every``
          commits; :meth:`resume` stitches bitwise from the newest one.
        """
        return self._run(state, steps=steps, schedule=schedule,
                         batches=batches, key=key, data=data,
                         batch_sizes=batch_sizes, poison=poison,
                         checkpoint_path=checkpoint_path,
                         checkpoint_every=checkpoint_every,
                         start=0, base_steps=None)

    def resume(self, checkpoint_path: str, state: SamplerState, *,
               steps: int, **kw):
        """Continue an interrupted ``run(checkpoint_path=...)`` bitwise.

        ``state`` is the same *initial* ensemble state the interrupted run
        started from (it supplies the carry's structure and shardings); the
        remaining args must repeat the interrupted call.  A missing
        checkpoint file starts the run from scratch (writing checkpoints to
        the same path); a truncated or bit-flipped one raises
        :class:`~repro.checkpoint.CorruptCheckpointError` loudly.  Returns
        ``(state, aux)`` where aux covers only the commits actually run.
        """
        if not os.path.exists(checkpoint_path):
            return self.run(state, steps=steps,
                            checkpoint_path=checkpoint_path, **kw)
        carry, done, base = self._load_run_checkpoint(checkpoint_path, state)
        if done >= steps:
            return carry, None
        return self._run(carry, steps=steps, start=done, base_steps=base,
                         checkpoint_path=checkpoint_path, **kw)

    def _run(self, state, *, steps, **kw):
        with _span("cluster.run", steps=steps, chains=self.num_chains):
            state, aux = self._drive(state, steps=steps, **kw)
        self._record_loads(aux)
        return state, aux

    def _record_loads(self, aux) -> None:
        """The held experts' loads of an MoE model's commits (the grad fn's
        ``expert_tokens``, (steps, C, layers, held)), when aux carries them:
        one host copy of that array."""
        if not isinstance(aux, dict) or "expert_tokens" not in aux:
            return
        loads = np.asarray(jax.device_get(aux["expert_tokens"]))
        self._m_moe_held.inc(float(loads.sum()))
        self._m_moe_load.observe_many(loads.ravel())

    def _schedule_inputs(self, state, schedule, steps, poison, base_steps):
        """-> (extra, commit_times, batch_info, base): the run's per-commit
        scan inputs, with read versions (and worker slots) rebased onto the
        initial commit counter ``base`` and copied to the device."""
        extra, commit_times, batch_info = self._compile_schedule(schedule,
                                                                 steps)
        staleness = (np.arange(steps, dtype=np.int64)[:, None] - extra["rv"])
        max_delay = int(staleness.max(initial=0))
        validate_staleness(max_delay, state.inner, context="schedule")
        self._m_staleness.observe_many(staleness.ravel())
        self._m_commits.inc(staleness.size)
        self._m_max_stale.set(float(max_delay))
        if poison is not None:
            pz = np.asarray(poison, bool)
            if pz.shape != (steps, self.num_chains):
                raise ValueError(
                    f"poison must be (steps, C) = ({steps}, "
                    f"{self.num_chains}), got {pz.shape}")
            if pz.any():
                extra["poison"] = pz
        n_faults = ((int((~extra["alive"]).sum()) if "alive" in extra else 0)
                    + (int(extra["poison"].sum()) if "poison" in extra else 0))
        if n_faults:
            self._m_faults.inc(n_faults)
        # schedule versions are relative to the run's first commit; rebase
        # onto the *initial* commit counter (the carried one on a fresh run,
        # the manifest's on a resume) so continuation runs keep the
        # endogenous staleness (step - read_version) equal to the schedule's
        # tau_k instead of silently clamping at the ring depth.
        base = np.asarray(state.step if base_steps is None else base_steps)
        extra["rv"] = jnp.asarray(extra["rv"] + base[None, :], jnp.int32)
        if self.worker_rng:
            # worker slots are schedule-relative too; rebase them the same
            # way so a continuation run folds fresh (wid, slot) pairs into
            # the noise keys instead of replaying the previous run's draws
            # (the carried chain key is deliberately untouched in this mode)
            extra["slot"] = jnp.asarray(
                extra["slot"] + base[None, :], jnp.int32)
        return extra, commit_times, batch_info, base

    def _drive(self, state, *, steps, schedule=None, batches=None, key=None,
               data=None, batch_sizes=None, poison=None, checkpoint_path=None,
               checkpoint_every=None, start=0, base_steps=None):
        with _span("cluster.schedule"):
            extra, commit_times, batch_info, base = self._schedule_inputs(
                state, schedule, steps, poison, base_steps)
        carry = self._as_carry(state)
        use_health = isinstance(carry, HealthState)
        chunk_post = None
        if use_health or checkpoint_path is not None:
            prev_health = [np.asarray(carry.health) if use_health else None]
            last_saved = [start]

            def chunk_post(done: int, st):
                if use_health:
                    st = self._heal(st, prev_health)
                if checkpoint_path is not None:
                    absolute = start + done
                    if (checkpoint_every is None
                            or absolute - last_saved[0] >= checkpoint_every
                            or absolute >= steps):
                        self._save_run_checkpoint(checkpoint_path, st,
                                                  absolute, base)
                        last_saved[0] = absolute
                return st

        if self.batch_policy != "fixed":
            if data is None:
                raise ValueError(f"batch_policy={self.batch_policy!r} needs "
                                 "a data= example stream passed to run()")
            if batches is not None:
                raise ValueError("pass either data= (heterogeneous masked "
                                 "windows) or batches=, not both")
            sizes, offs = self._compile_batch_plan(batch_info, batch_sizes,
                                                   steps)
            n_data = int(jax.tree_util.tree_leaves(data)[0].shape[0])
            extra["size"] = sizes
            extra["off"] = (offs % n_data).astype(np.int32)
            evals = np.cumsum(sizes.astype(np.int64), axis=0)
            self._m_grad_evals.inc(int(sizes.sum()))
            if start:
                # resume: drop the commits already executed.  Checkpoints
                # land on chunk boundaries, so the remaining chunk grid (and
                # with it every bucket rung choice) matches the
                # uninterrupted run's — a precondition for bitwise stitching.
                extra = jax.tree_util.tree_map(lambda x: x[start:], extra)
                sizes = sizes[start:]
                evals = evals[start:]
                if commit_times is not None:
                    commit_times = commit_times[start:]

            def chunk_info(done: int, n: int):
                rung = bucket_size(int(sizes[done:done + n].max()),
                                   self.buckets)
                return (rung,)

            return drive_chunks(
                self._run_masked_chunk, carry, steps=steps - start,
                chunk_size=self.chunk_size, hooks=self.hooks,
                collect_aux=self.collect_aux, extra=extra, batches=data,
                slice_batches=False, chunk_info=chunk_info,
                commit_times=commit_times, host_aux={"grad_evals": evals},
                chunk_post=chunk_post)

        # explicit batches follow the per_chain_batches contract; generated
        # ones always carry a chain axis (one key per (step, chain))
        per_chain = (self.per_chain_batches if batches is not None
                     else self._make_batches is not None)
        run_chunk = self._chunk_per_chain if per_chain else self._chunk_shared

        def gen_batches(key, n):
            key, sub = jax.random.split(key)
            chunk_keys = jax.random.split(sub, n * self.num_chains)
            chunk_keys = chunk_keys.reshape(
                (n, self.num_chains) + chunk_keys.shape[1:])
            return key, self._make_batches(chunk_keys)

        if start:
            extra = jax.tree_util.tree_map(lambda x: x[start:], extra)
            if commit_times is not None:
                commit_times = commit_times[start:]
            if batches is not None:
                batches = jax.tree_util.tree_map(lambda x: x[start:], batches)
            if self._make_batches is not None and key is not None:
                # fast-forward the batch key stream: one split was consumed
                # per completed chunk (checkpoints land on chunk boundaries)
                for _ in range(start // self.chunk_size):
                    key, _ = jax.random.split(key)

        return drive_chunks(
            run_chunk, carry, steps=steps - start,
            chunk_size=self.chunk_size,
            hooks=self.hooks, collect_aux=self.collect_aux,
            extra=extra, batches=batches,
            gen_batches=gen_batches if self._make_batches is not None else None,
            key=key, commit_times=commit_times, chunk_post=chunk_post)
