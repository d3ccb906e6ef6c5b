"""The request-level serving front door every engine shares.

PR 3 grew predictive serving and PR 5 streaming decode as *batch*-level
APIs: callers hand a whole query batch to :meth:`ServeEngine.serve` or a
whole prompt batch to :meth:`DecodeEngine.generate`, and every row in the
batch lives and dies together.  Continuous batching breaks that coupling —
a scheduler admits and retires *individual sequences* against shared device
state — so the unit of work has to become the single request.  This module
defines that unit:

- :class:`Request` — one sequence (or one predictive query): prompt tokens,
  a per-request generation budget, an optional per-request sampling key,
  and a scheduling priority;
- :class:`Completion` — its result: generated tokens, optional per-token
  BMA logits, a finish reason, and host-clock timing
  (submitted/admitted/first token/finished);
- :class:`Endpoint` — the shared ``submit()`` / ``drain()`` surface.
  :meth:`ServeEngine.serve` and :meth:`DecodeEngine.generate` are thin
  shims over it (kept bitwise-compatible — pinned in
  ``tests/test_api.py``), and
  :class:`~repro.cluster.paged.PagedDecodeEngine` consumes it natively
  with slot-level admission;
- :class:`BankEngine` — the constructor/plumbing base every chain-bank
  engine shares: one ``from_checkpoint`` / ``from_cluster`` signature, one
  mesh-divisibility check and bank-sharding layout, one
  :class:`HostScratch` + instrument-counter setup, and the
  gather-then-replicated-:func:`~repro.models.predictive.bma_logits`
  collective wrapper the decode engines pin their sharded == unsharded
  bitwise contract on.
"""

from __future__ import annotations

import functools
import itertools
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.analysis.instrument import Counters as _Counters, counters as _counters
from repro.models.predictive import bma_logits
from repro.obs.metrics import registry as _registry
from repro.obs.trace import now as _now
from repro.samplers.base import SamplerState

PyTree = Any

#: finish reasons a :class:`Completion` can carry
FINISH_LENGTH = "length"      # generated its full max_new_tokens budget
FINISH_QUERY = "query"        # predictive query: answered in one shot
FINISH_DEADLINE = "deadline"  # deadline expired (shed or cut short)

#: delivery status a :class:`Completion` can carry
STATUS_OK = "ok"            # full result
STATUS_TIMEOUT = "timeout"  # deadline hit mid-decode: partial tokens
STATUS_SHED = "shed"        # deadline hit before admission: no tokens

_REQUEST_IDS = itertools.count(1)


class QueueFullError(RuntimeError):
    """Backpressure: the engine's waiting queue is at ``max_waiting`` —
    the caller must drain (or step) before submitting more work."""


@dataclass
class Request:
    """One unit of serving work.

    ``tokens`` is a 1-D prompt token array for decode engines, or one query
    (any pytree row) for predictive engines.  ``max_new_tokens`` is this
    request's *own* generation budget — requests with different budgets
    share a continuous batch without convoying (0 = predictive query).
    ``key`` is the per-request sampling key (``None`` = greedy; batch-shim
    engines share one key across the rows of a legacy batched call, the
    paged scheduler folds it per emitted position so an evicted-and-
    replayed request resamples identically).  Higher ``priority`` admits
    first and may preempt lower-priority running slots.  ``request_id`` is
    stamped by :meth:`Endpoint.submit`.

    ``deadline_ms`` (optional) is a host-clock latency budget measured from
    submission: the paged scheduler sheds the request
    (:data:`STATUS_SHED`) if it expires while still waiting, and cuts it
    short with partial tokens (:data:`STATUS_TIMEOUT`) if it expires while
    decoding.  ``None`` — the default — never expires.
    """

    tokens: Any
    max_new_tokens: int = 0
    key: Optional[jax.Array] = None
    priority: int = 0
    request_id: Optional[int] = None
    timing: dict = field(default_factory=dict)
    deadline_ms: Optional[float] = None


@dataclass
class Completion:
    """The finished result of one :class:`Request`.

    ``tokens`` is the generated ``(n,)`` int32 host array (empty for
    predictive queries); ``logits`` the per-token BMA log-probability block
    ``(n, V)`` when the engine returns logits, else ``None``;
    ``finish_reason`` one of :data:`FINISH_LENGTH` / :data:`FINISH_QUERY`;
    ``timing`` host-clock seconds (:func:`repro.obs.trace.now`) for
    ``submitted`` / ``admitted`` / ``first_token`` / ``finished`` plus an
    ``evictions`` count under the preempting scheduler — ``first_token``
    is when the first generated token became *available on host* (batch
    engines deliver at drain, so it equals ``finished`` there; the paged
    scheduler emits it at admission prefill).  ``stats`` carries the
    per-query :class:`~repro.cluster.serve.ServeResult` row on predictive
    endpoints.  ``status`` is the delivery outcome: :data:`STATUS_OK`
    (full result), :data:`STATUS_TIMEOUT` (deadline hit mid-decode —
    ``tokens`` holds the partial prefix), or :data:`STATUS_SHED`
    (deadline hit before admission — ``tokens`` is empty).
    """

    request_id: int
    tokens: np.ndarray
    logits: Optional[np.ndarray]
    finish_reason: str
    timing: dict
    stats: Optional[Any] = None
    status: str = STATUS_OK


class HostScratch:
    """Reusable host-side pad buffers, one per (bucket rung, leaf).

    Padding a request up its bucket rung is shape-varying glue that must
    stay in numpy on the serving hot path — but a fresh ``np.concatenate``
    per request still allocates (and touches) a buffer every call.  This
    keeps one scratch array per ``(rung, leaf key, trailing shape, dtype)``
    and rewrites it in place, so a steady-state request stream performs
    **zero** per-request allocations on the padding path (``allocs`` stops
    growing once every rung has been seen — asserted by the serve/decode
    benches).  Reuse is safe because ``jit`` copies host arrays to device
    synchronously at dispatch.

    Every buffer creation is reported to ``counters``
    (a :class:`repro.analysis.instrument.Counters` handle) when one is
    given, so an :func:`~repro.analysis.instrument.instrument` region around
    a warm request stream sees zero pad-alloc events.
    """

    def __init__(self, counters: Optional[_Counters] = None):
        self._bufs: dict = {}
        self.allocs = 0  # scratch-buffer creations, NOT per-request work
        self._counters = counters

    def get(self, key, shape, dtype) -> np.ndarray:
        """The scratch buffer for ``key`` (caller fills it)."""
        k = (key, tuple(shape), np.dtype(dtype).str)
        buf = self._bufs.get(k)
        if buf is None:
            buf = np.empty(shape, dtype)
            self._bufs[k] = buf
            self.allocs += 1
            if self._counters is not None:
                self._counters.pad_alloc()
        return buf

    def pad(self, x: np.ndarray, n: int, key=0) -> np.ndarray:
        """``x`` with its leading axis padded to ``n`` by edge-replicating
        the last row, written into the reused scratch."""
        q = x.shape[0]
        if q == n:
            return x  # jit transfers host arrays; caller's buffer intact
        buf = self.get(("pad", key), (n,) + x.shape[1:], x.dtype)
        buf[:q] = x
        buf[q:] = x[-1:]
        return buf


class Endpoint:
    """The ``submit()`` / ``drain()`` surface every serving engine exposes.

    ``submit`` enqueues one :class:`Request` and returns its id; ``drain``
    runs everything pending to completion and returns the
    :class:`Completion` list.  Batch engines group pending requests back
    into their legacy batched programs (bitwise-identical to direct batch
    calls); the paged scheduler interleaves them at slot granularity.
    Subclasses implement ``_drain(requests)``.
    """

    def submit(self, request: Request) -> int:
        """Enqueue one request; returns its stamped ``request_id``.

        Engines with a ``max_waiting`` bound reject submissions once the
        waiting queue is full — :class:`QueueFullError`, counted under
        ``requests.rejected`` — instead of growing it without limit."""
        limit = getattr(self, "max_waiting", None)
        if limit is not None and self._queue_depth() >= limit:
            _registry().counter(
                "requests.rejected",
                "submissions refused by max_waiting backpressure").inc()
            raise QueueFullError(
                f"waiting queue holds {self._queue_depth()} requests "
                f"(max_waiting={limit}); drain() or step() before "
                "submitting more")
        if request.request_id is None:
            request.request_id = next(_REQUEST_IDS)
        request.timing.setdefault("submitted", _now())
        self._validate_request(request)
        self._pending.append(request)
        return request.request_id

    def drain(self) -> list:
        """Run every pending request to completion; returns Completions.

        Always calls through to the engine's ``_drain`` — engines with
        internal scheduler state (waiting queues, occupied slots) finish
        in-flight work even when nothing new is pending."""
        reqs, self._pending = list(self._pending), []
        return self._drain(reqs)

    def _queue_depth(self) -> int:
        """Requests counted against ``max_waiting`` (engines with internal
        waiting queues — the paged scheduler — add theirs)."""
        return len(self._pending)

    def _validate_request(self, request: Request) -> None:
        del request  # engines override with their admission checks

    def _drain(self, requests: list) -> list:
        raise NotImplementedError


class BankEngine(Endpoint):
    """Shared plumbing for engines serving a chain-stacked parameter bank.

    Concrete engines (:class:`~repro.cluster.serve.ServeEngine`,
    :class:`~repro.cluster.decode.DecodeEngine`,
    :class:`~repro.cluster.paged.PagedDecodeEngine`) are dataclasses with
    ``params`` / ``mesh`` / ``chain_axis`` fields; this base owns what they
    all repeat: bank validation + chain counting + scratch/counter setup
    (:meth:`_init_bank`), the mesh-divisibility check and bank sharding
    layout (:meth:`_shard_bank`), the gather-then-replicated BMA collective
    wrapper (:meth:`_wrap_bma`), and one constructor signature
    (:meth:`from_checkpoint` / :meth:`from_cluster`) — the migration table
    lives in ``docs/SERVING.md``.
    """

    #: the dataclass field the positional constructor argument binds to
    #: (``predict_fn`` for predictive engines, ``model`` for decode engines)
    _FRONT_FIELD = "model"

    # -- shared __post_init__ plumbing ---------------------------------------
    def _init_bank(self, label: str) -> None:
        """Validate the bank, count chains, sort bucket ladders, and wire
        the instrument counters + host pad scratch + request queue."""
        leaves = jax.tree_util.tree_leaves(self.params)
        if not leaves:
            raise ValueError("params bank is empty")
        self.num_chains = int(leaves[0].shape[0])
        for name in ("buckets", "prompt_buckets"):
            ladder = getattr(self, name, None)
            if ladder is not None:
                setattr(self, name, sorted(int(b) for b in ladder))
        self._counters = _counters(label)
        self._scratch = HostScratch(self._counters)
        self._host_scratch = self._scratch  # legacy ServeEngine attr name
        self._pending: list = []

    def _shard_bank(self) -> None:
        """Check chain divisibility over the mesh and device_put the bank
        into its sharded layout (no-op without a mesh)."""
        if self.mesh is None:
            return
        n_shards = self.mesh.shape[self.chain_axis]
        if self.num_chains % n_shards:
            raise ValueError(
                f"num_chains={self.num_chains} must be divisible by mesh "
                f"axis {self.chain_axis!r} (size {n_shards})")
        self.params = jax.device_put(self.params, self._bank_shardings())

    def _bank_shardings(self):
        """Per-leaf NamedShardings for the params bank: chain axis over
        ``chain_axis``; with ``shard_params`` the single-chain tensor-
        parallel specs (``partition_tree``) compose behind it (2-D)."""
        if not getattr(self, "shard_params", False):
            s = NamedSharding(self.mesh, P(self.chain_axis))
            return jax.tree_util.tree_map(lambda _: s, self.params)
        from repro.models.common import partition_tree

        cfg = self._model.cfg
        like = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), self.params)
        specs = partition_tree(like, cfg.param_sharding,
                               model_size=self.mesh.shape.get("model"),
                               cfg=cfg)
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, P(self.chain_axis, *s)), specs,
            is_leaf=lambda s: isinstance(s, P))

    def _wrap_bma(self, body, in_specs, out_specs, reduce_full=bma_logits):
        """Wrap ``body(reduce, *args)`` under the engine's collective layout.

        ``reduce`` maps the per-chain block (logits ``(C, B, V)`` on decode
        engines, predictions ``(C, Q, ...)`` on predictive ones) to the
        replicated ensemble law: plain ``reduce_full`` (the BMA reduce by
        default) unsharded; an ``all_gather`` of the model-size-independent
        block then the *identical* replicated reduce under the chain-sharded
        ``shard_map`` — sharded and unsharded serving are two programs and
        agree within :data:`~repro.models.predictive.LOGP_ATOL`; a
        replication ``with_sharding_constraint`` then the same reduce under
        GSPMD when ``shard_params``.  ``in_specs`` / ``out_specs`` are the
        shard_map specs (``P(ax)`` on chain-stacked args, ``P()`` on
        replicated ones); they are ignored on the unsharded and GSPMD paths.
        """
        if self.mesh is None:
            return functools.partial(body, reduce_full)
        if getattr(self, "shard_params", False):
            rep = NamedSharding(self.mesh, P())

            def reduce(per_chain):  # pin gather-then-reduce under GSPMD
                gathered = jax.lax.with_sharding_constraint(per_chain, rep)
                return reduce_full(gathered)

            return functools.partial(body, reduce)
        ax = self.chain_axis

        def sharded_reduce(local):  # (C/shards, B, ...) -> replicated
            full = jax.lax.all_gather(local, ax, axis=0, tiled=True)
            return reduce_full(full)

        return jax.shard_map(functools.partial(body, sharded_reduce),
                             mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    # -- shared observability views ------------------------------------------
    @property
    def num_traces(self) -> int:
        """Jit traces so far (one per shape rung) — a thin view over the
        engine's :mod:`repro.analysis.instrument` counters."""
        return self._counters.traces

    @property
    def num_host_pad_allocs(self) -> int:
        """Host scratch-buffer creations so far — one per (bucket rung,
        leaf), NOT one per request; the serve/decode benches assert this
        stops growing once the stream's rungs have all been seen."""
        return self._counters.pad_allocs

    # -- unified constructors -------------------------------------------------
    @classmethod
    def from_cluster(cls, state: SamplerState | PyTree, front=None, **kw):
        """Serve directly from a (possibly still sharded) ClusterEngine
        state — or any chain-stacked params pytree.  ``front`` is the
        engine's front argument (``model`` for decode engines,
        ``predict_fn`` for predictive ones); both may also be passed by
        keyword.

        A :class:`~repro.cluster.executor.HealthState` (or any state
        carrying a ``health`` mask) serves **degraded**: quarantined chains
        are dropped from the bank and the BMA averages the survivors, so a
        partially-poisoned ensemble keeps answering instead of serving NaN
        logits.  An all-quarantined bank raises."""
        params = getattr(state, "params", state)
        health = getattr(state, "health", None)
        if health is not None:
            h = np.asarray(health)
            if not h.any():
                raise ValueError(
                    "every chain is quarantined — no healthy bank to serve")
            if not h.all():
                keep = np.flatnonzero(h)
                params = jax.tree_util.tree_map(lambda x: x[keep], params)
                _registry().gauge(
                    "chains.unhealthy",
                    "chains currently quarantined").set(float(
                        h.size - keep.size))
        if front is not None:
            kw.setdefault(cls._FRONT_FIELD, front)
        return cls(params=params, **kw)

    @classmethod
    def from_checkpoint(cls, path: str, like: PyTree = None, front=None, *,
                        num_chains: Optional[int] = None, **kw):
        """Restore a bank saved by :meth:`ClusterEngine.save_ensemble` (or
        broadcast a single-model checkpoint to ``num_chains``) and serve it.

        One signature for every engine: ``(path, like, model_or_predict_fn,
        ...)`` where ``like`` is the *single-chain* params structure and the
        third argument is the engine's front argument (``model`` /
        ``predict_fn``), also accepted by keyword.  The legacy
        ``DecodeEngine.from_checkpoint(path, model, like)`` positional order
        is detected (a model/config in the ``like`` seat) and swapped, so
        pre-PR-9 call sites keep working — see the migration table in
        ``docs/SERVING.md``.
        """
        if _looks_like_model(like) and not _looks_like_model(front):
            like, front = front, like  # legacy (path, model, like) order
        if front is not None:
            kw.setdefault(cls._FRONT_FIELD, front)
        from repro.checkpoint import restore_ensemble

        params = restore_ensemble(path, like, num_chains=num_chains)
        return cls(params=params, **kw)


def _looks_like_model(x) -> bool:
    """A Model (has .cfg) or a raw config (has .d_model) — never a params
    pytree or a predict_fn."""
    return hasattr(x, "cfg") or hasattr(x, "d_model")
