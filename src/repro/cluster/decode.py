"""Streaming Bayesian-model-averaged decoding from the sharded chain bank.

The paper's bet is that delayed-gradient SGLD buys wall-clock without
hurting convergence in measure; serving makes the same bet at inference
time.  A converged :class:`~repro.cluster.executor.ClusterEngine` bank is C
posterior samples of one transformer — the stale-chain ensemble of Chen et
al.'s SG-MCMC predictive — and :class:`DecodeEngine` streams multi-token
generations whose every token is drawn from the *Bayesian model average*
over the bank: per token, each chain runs one cached decode step, the
per-chain logits are reduced to the posterior-predictive token law
(:func:`~repro.models.predictive.bma_logits`), and the sampled/argmaxed
token feeds back into every chain's cache.

Hot-path discipline (the decode loop is the hottest per-token path in the
system):

- **KV-cache bank**: one per-chain decode cache per batch bucket rung,
  allocated once (``Model.init_cache_bank`` — every leaf gains the leading
  chain axis), donated to the jitted program and updated in place across
  serve steps.  No per-request cache allocation.  Rungs live in an LRU
  (capped at ``max_cache_rungs``): an adversarial mix of batch sizes evicts
  the coldest rung's bank instead of growing device memory without bound.
- **One trace per (bucket, max_new_tokens)**: prompts are padded up the
  shared bucket ladder in both batch and length (numpy scratch, reused per
  rung), the true ``prompt_len`` rides along as a traced scalar, and the
  whole prefill + ``lax.scan`` decode loop compiles exactly once per
  ``(B rung, T rung, max_new_tokens)`` triple.  No per-token dispatch from
  Python: the scan *is* the token loop.
- **Collective layout** (``mesh=``): the bank shards over ``chain_axis``;
  each shard vmaps the cached single-token forward over its local chains
  and only the ``(C, B, V)`` logit block crosses shards via ``all_gather``
  each token, after which every shard runs the identical replicated BMA
  reduce + argmax — so sharded and unsharded decode are bitwise-equal (the
  serve-module parity contract) and every shard feeds the same token back.
- **2-D banks** (``shard_params=True``): the chain axis composes with the
  repo's ``model``-axis tensor-parallel parameter sharding
  (:func:`~repro.models.common.partition_tree` with the chain axis
  prepended) under GSPMD, with the logit block constrained replicated
  before the same BMA reduce — a (chains x tensor-parallel) bank of large
  models streams without gathering parameters anywhere.  Tensor-parallel
  contractions psum over shards, so this path trades the bitwise guarantee
  for HBM headroom; the chain-sharded ``shard_map`` path keeps it.

Since PR 9 the engine is also a request-level
:class:`~repro.cluster.api.Endpoint`: ``submit()`` enqueues individual
prompt :class:`~repro.cluster.api.Request`\\ s and ``drain()`` stacks
compatible ones (same prompt length, budget, and key) back through the
bucketed batch program.  ``generate()`` is a thin shim over that path and
stays bitwise-identical to the pre-PR-9 batch-level API (pinned in
``tests/test_api.py``).  For slot-level continuous batching — admission
the moment any sequence finishes — see
:class:`~repro.cluster.paged.PagedDecodeEngine`.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.cluster.api import (
    FINISH_LENGTH,
    BankEngine,
    Completion,
    Request,
)
from repro.obs.metrics import LATENCY_MS_BUCKETS, registry as _registry
from repro.obs.trace import now as _now, span as _span
from repro.utils import bucket_size

PyTree = Any


class DecodeResult(NamedTuple):
    """One streamed generation: ``tokens`` is ``(B, max_new_tokens)`` int32
    on host; ``logits`` is the per-token BMA log-probability block
    ``(B, max_new_tokens, V)`` when the engine was built with
    ``return_logits=True``, else ``None``."""

    tokens: np.ndarray
    logits: Optional[np.ndarray]


@dataclass
class DecodeEngine(BankEngine):
    """Streaming multi-token BMA generation over a chain-stacked bank.

    ``model`` is the :class:`~repro.models.transformer.Model` (or anything
    with a ``.cfg``) the bank parameterizes — the engine rebuilds its own
    serving copy (``remat=False``, fused decode per ``fused=``); ``params``
    is the chain-stacked bank ``(C, ...)``.  ``generate(tokens, n)`` pads
    the prompt batch up the bucket ladder, prefills the rung's persistent
    KV-cache bank, and drives one scan-compiled decode loop; ``key=None``
    decodes greedily, a PRNG key samples from the BMA token law.
    ``max_cache_rungs`` caps how many batch rungs keep a resident KV bank
    (least-recently-used rung evicted beyond it).
    """

    model: Any
    params: PyTree
    max_seq: int = 256
    buckets: Optional[Sequence[int]] = None         # batch-size ladder
    prompt_buckets: Optional[Sequence[int]] = None  # prompt-length ladder
    mesh: Any = None
    chain_axis: str = "data"
    shard_params: bool = False
    fused: bool = False
    return_logits: bool = False
    max_cache_rungs: int = 8

    _FRONT_FIELD = "model"

    def __post_init__(self):
        from repro.models.transformer import Model

        self._init_bank("DecodeEngine")
        cfg = self.model.cfg if hasattr(self.model, "cfg") else self.model
        self._model = Model(cfg, mesh=None, remat=False,
                            decode_fused=self.fused)
        self._model._require_stacked_attention("DecodeEngine")
        self._cache: OrderedDict = OrderedDict()  # B rung -> KV-cache bank
        reg = _registry()
        self._m_requests = reg.counter("decode.requests", "generate() calls")
        self._m_tokens = reg.counter("decode.tokens",
                                     "tokens generated (true batch rows)")
        self._m_token_ms = reg.histogram(
            "decode.per_token_ms", LATENCY_MS_BUCKETS,
            "request wall time / max_new_tokens (amortized; the decode "
            "loop is one fused scan)")
        self._m_batch_util = reg.gauge(
            "decode.batch_utilization", "last request's B / batch rung")
        self._m_bank_rungs = reg.gauge(
            "decode.bank_rungs", "KV-cache bank rungs resident")
        self._m_bank_evictions = reg.counter(
            "decode.bank_evictions",
            "KV-cache rungs dropped by the max_cache_rungs LRU cap")
        self._shard_bank()
        self._run = jax.jit(self._core, static_argnums=(0, 1),
                            donate_argnums=(3,))

    # -- the traced program ---------------------------------------------------
    def _core(self, max_new: int, greedy: bool, params, cache, tokens,
              prompt_len, key):
        # python side effect: runs once per (rung, max_new) trace
        self._counters.trace("decode")
        ax = self.chain_axis

        def body(reduce, params, cache, tokens, prompt_len, key):
            return self._stream(params, cache, tokens, prompt_len, key,
                                max_new, greedy, reduce=reduce)

        return self._wrap_bma(
            body, in_specs=(P(ax), P(ax), P(), P(), P()),
            out_specs=(P(), P(), P(ax)))(params, cache, tokens, prompt_len,
                                         key)

    def _stream(self, params, cache, tokens, prompt_len, key, max_new: int,
                greedy: bool, *, reduce):
        """Prefill the cache bank, then one ``lax.scan`` over the decode
        steps — traced exactly once per (bucket, max_new) pair."""
        model = self._model
        prefill = jax.vmap(model.prefill_cache, in_axes=(0, None, 0, None))
        last, cache = prefill(params, tokens, cache, prompt_len)  # (C, B, V)
        l0 = reduce(last)
        keys = jax.random.split(key, max_new)

        def select(logp, k):
            if greedy:
                return jnp.argmax(logp, axis=-1).astype(jnp.int32)
            return jax.random.categorical(k, logp, axis=-1).astype(jnp.int32)

        tok0 = select(l0, keys[0])  # (B,)
        decode = jax.vmap(model.serve_step, in_axes=(0, 0, None, None))
        want_logits = self.return_logits
        none = jnp.zeros((0,), jnp.float32)

        def step(carry, k_t):
            tok, pos, cache = carry
            per_chain, cache = decode(params, cache, tok[:, None], pos)
            logp = reduce(per_chain[:, :, 0])  # (B, V)
            nxt = select(logp, k_t)
            return (nxt, pos + 1, cache), (nxt, logp if want_logits else none)

        (_, _, cache), (toks, logps) = jax.lax.scan(
            step, (tok0, prompt_len, cache), keys[1:])
        tokens_out = jnp.concatenate([tok0[None], toks], axis=0).T
        if want_logits:
            logits_out = jnp.concatenate([l0[None], logps],
                                         axis=0).transpose(1, 0, 2)
        else:
            logits_out = none
        return tokens_out, logits_out, cache

    # -- KV-cache bank (LRU over batch rungs) ---------------------------------
    def _rung_cache(self, b_rung: int):
        cache = self._cache.pop(b_rung, None)
        if cache is None:
            cache = self._model.init_cache_bank(self.num_chains, b_rung,
                                                self.max_seq)
            if self.mesh is not None:
                cache = jax.device_put(
                    cache, NamedSharding(self.mesh, P(self.chain_axis)))
        return cache

    def _store_rung_cache(self, b_rung: int, cache) -> None:
        # pop-on-read + insert-on-write keeps the OrderedDict in recency
        # order, so the front is always the least-recently-used rung
        self._cache[b_rung] = cache
        while len(self._cache) > self.max_cache_rungs:
            self._cache.popitem(last=False)
            self._m_bank_evictions.inc()
        self._m_bank_rungs.set(float(len(self._cache)))

    # -- request-level endpoint -----------------------------------------------
    def _validate_request(self, request: Request) -> None:
        tokens = np.asarray(request.tokens)
        if tokens.ndim != 1:
            raise ValueError(
                f"a decode Request carries one 1-D prompt, got shape "
                f"{tokens.shape}")
        if request.max_new_tokens < 1:
            raise ValueError(
                f"need max_new_tokens >= 1, got {request.max_new_tokens}")
        t_rung = bucket_size(tokens.shape[0], self.prompt_buckets)
        if not self._model.cfg.sliding_window and \
                t_rung + request.max_new_tokens > self.max_seq:
            # under a sliding window the ring overwriting its oldest slot is
            # exactly the attention semantics; without one it would silently
            # drop real context from every remaining step
            raise ValueError(
                f"prompt rung {t_rung} + max_new_tokens "
                f"{request.max_new_tokens} overflows the {self.max_seq}-slot "
                "cache of a full-attention model; raise max_seq")
        request.tokens = tokens

    def _drain(self, requests):
        """Stack compatible pending prompts — same length, same budget, same
        sampling key — into batched :meth:`_generate_batch` calls (in first-
        submission order) and hand every request its row back as a
        :class:`~repro.cluster.api.Completion`."""
        groups: OrderedDict = OrderedDict()
        for r in requests:
            sig = (r.tokens.shape[0], int(r.max_new_tokens),
                   id(r.key) if r.key is not None else None)
            groups.setdefault(sig, []).append(r)
        out = {}
        for (_, max_new, _), rows in groups.items():
            batch = np.stack([r.tokens for r in rows])
            res = self._generate_batch(batch, max_new, rows[0].key)
            t_done = _now()
            for i, r in enumerate(rows):
                # batch engines deliver whole generations at drain: the
                # first token becomes host-visible when the batch does
                r.timing["first_token"] = r.timing["finished"] = t_done
                out[r.request_id] = Completion(
                    request_id=r.request_id, tokens=res.tokens[i],
                    logits=(res.logits[i] if res.logits is not None
                            else None),
                    finish_reason=FINISH_LENGTH, timing=r.timing)
        return [out[r.request_id] for r in requests]

    # -- serving --------------------------------------------------------------
    def _generate_batch(self, tokens: np.ndarray, max_new_tokens: int,
                        key: Optional[jax.Array]) -> DecodeResult:
        """The batch-level program: pad one (B, T) prompt batch up its rung
        pair, prefill the rung's persistent cache bank, run the scan-
        compiled decode loop, trim on host."""
        B, T = tokens.shape
        b_rung = bucket_size(B, self.buckets)
        t_rung = bucket_size(T, self.prompt_buckets)
        t_start = _now()
        with _span("decode.generate", B=B, T=T, b_rung=b_rung, t_rung=t_rung,
                   new_tokens=int(max_new_tokens), chains=self.num_chains):
            buf = self._scratch.get(("prompt", b_rung, t_rung),
                                    (b_rung, t_rung), np.int32)
            buf[:B, :T] = tokens
            buf[:B, T:] = tokens[:, -1:]  # right pad: causally invisible
            buf[B:] = buf[B - 1]          # edge-replicate padded batch rows
            cache = self._rung_cache(b_rung)
            greedy = key is None
            k = jnp.zeros((2,), jnp.uint32) if greedy else key
            toks, logps, cache = self._run(
                int(max_new_tokens), greedy, self.params, cache, buf,
                np.asarray(T, np.int32), k)
            self._store_rung_cache(b_rung, cache)  # donated in, reused next
            out = np.asarray(toks)[:B]  # blocks: the span sees real latency
        self._m_requests.inc()
        self._m_tokens.inc(B * int(max_new_tokens))
        self._m_token_ms.observe((_now() - t_start) * 1e3 / max_new_tokens)
        self._m_batch_util.set(B / b_rung)
        return DecodeResult(
            tokens=out,
            logits=np.asarray(logps)[:B] if self.return_logits else None)

    def generate(self, tokens, max_new_tokens: int,
                 key: Optional[jax.Array] = None) -> DecodeResult:
        """Stream ``max_new_tokens`` BMA tokens from a prompt batch.

        ``tokens`` is a host or device ``(B, T)`` int array (every prompt in
        a request shares T, as in :class:`ServeEngine`'s batched queries);
        mixed request streams bucket on both axes.  Greedy when ``key`` is
        None, else each token is sampled from the BMA predictive law.  The
        rows travel as individual :class:`~repro.cluster.api.Request`\\ s
        through ``submit()``/``drain()``, which stacks them straight back
        into one batch — bitwise-identical to the pre-PR-9 path.  Returns
        host arrays trimmed to the true batch.
        """
        tokens = np.asarray(tokens)
        if tokens.ndim != 2:
            raise ValueError(f"prompt batch must be (B, T), got {tokens.shape}")
        ids = [self.submit(Request(tokens=row,
                                   max_new_tokens=int(max_new_tokens),
                                   key=key))
               for row in tokens]
        by_id = {c.request_id: c for c in self.drain()}
        rows = [by_id[i] for i in ids]
        return DecodeResult(
            tokens=np.stack([c.tokens for c in rows]),
            logits=(np.stack([c.logits for c in rows])
                    if self.return_logits else None))

    __call__ = generate
