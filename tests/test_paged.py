"""cluster.paged: continuous batching over the paged KV bank.

The paged serving contract, pinned:

- a single-slot paged engine is **bitwise-equal** (tokens AND BMA logits)
  to the contiguous :class:`DecodeEngine` on the same request;
- at ``num_slots > 1`` the step batch runs at width S, so XLA may pick a
  different (gemm vs gemv) matmul schedule than the contiguous B=1 path —
  the honest invariant is **slot-occupancy invariance**: a request decodes
  bitwise-identically whether it runs alone in the engine or interleaved
  with a full complement of neighbours;
- admission is slot-level: a waiting prompt is prefilled the moment a
  sequence finishes or is evicted, never at batch boundaries;
- priority eviction requeues the victim and replays it bitwise (sampling
  keys are folded per absolute position, so a replay resamples the exact
  same tokens);
- the engine compiles one prefill trace per prompt rung plus ONE step
  trace for its whole lifetime, and a warm stream never retraces or
  allocates pad scratch;
- the fused Pallas paged step is bitwise-equal to its oracle and slots
  into the engine without changing tokens.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.instrument import instrument
from repro.cluster import DecodeEngine, PagedDecodeEngine
from repro.cluster.api import Request
from repro.cluster.paged import PageAllocator
from repro.configs import get_reduced
from repro.kernels.ops import fused_paged_decode_step
from repro.kernels.ref import paged_decode_step_ref
from repro.models.predictive import LOGP_ATOL
from repro.models.transformer import Model, init_params

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
C = 4


@pytest.fixture(scope="module")
def cfg():
    return get_reduced("qwen3-4b")


@pytest.fixture(scope="module")
def model(cfg):
    return Model(cfg, remat=False)


@pytest.fixture(scope="module")
def bank(cfg):
    return jax.vmap(lambda k: init_params(k, cfg))(
        jax.random.split(jax.random.PRNGKey(0), C))


def prompts_and_budgets(cfg, n=6, seed=0):
    rng = np.random.default_rng(seed)
    lens = [5, 3, 7, 2, 6, 4][:n]
    budgets = [6, 2, 9, 1, 12, 7][:n]
    toks = [rng.integers(0, cfg.vocab_size, (t,), dtype=np.int32)
            for t in lens]
    return toks, budgets


def fresh(model, bank, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq", 32)
    kw.setdefault("decode_chunk", 4)
    return PagedDecodeEngine(model=model, params=bank, **kw)


# ---------------------------------------------------------------------------
# page allocator
# ---------------------------------------------------------------------------
def test_page_allocator_reserves_garbage_page_and_round_trips():
    a = PageAllocator(9)  # pages 1..8 usable, page 0 is the garbage sink
    assert a.free_pages == 8
    got = a.alloc(3)
    assert got is not None and len(got) == 3 and 0 not in got
    assert a.free_pages == 5
    assert a.alloc(6) is None          # insufficient: no partial grant
    assert a.free_pages == 5           # failed alloc takes nothing
    a.free(got)
    assert a.free_pages == 8
    assert sorted(a.alloc(8)) == list(range(1, 9))


def test_page_allocator_rejects_bad_frees():
    a = PageAllocator(5)
    with pytest.raises(ValueError, match="bad page id"):
        a.free([0])                    # the garbage page is never owned
    with pytest.raises(ValueError, match="bad page id"):
        a.free([5])
    with pytest.raises(ValueError, match="need >= 2 pages"):
        PageAllocator(1)


# ---------------------------------------------------------------------------
# parity contract
# ---------------------------------------------------------------------------
def test_single_slot_bitwise_vs_contiguous_engine(cfg, model, bank):
    """A num_slots=1 paged engine IS the contiguous engine, bit for bit:
    same tokens, same per-token BMA logits, page indirection invisible."""
    ref = DecodeEngine(model=model, params=bank, max_seq=32,
                       return_logits=True)
    eng = fresh(model, bank, num_slots=1, return_logits=True)
    toks, budgets = prompts_and_budgets(cfg, n=3)
    for t, n in zip(toks, budgets):
        want = ref.generate(t[None], n)
        rid = eng.submit(Request(tokens=t, max_new_tokens=n))
        got = {c.request_id: c for c in eng.drain()}[rid]
        assert np.array_equal(got.tokens, want.tokens[0])
        assert np.array_equal(got.logits, want.logits[0])
        assert got.finish_reason == "length"


def test_slot_occupancy_invariance(cfg, model, bank):
    """A request's tokens and logits are bitwise-identical whether it runs
    alone in the 4-slot engine or packed in with five neighbours — garbage
    writes from idle slots and physical page placement never leak in."""
    toks, budgets = prompts_and_budgets(cfg)
    solo_eng = fresh(model, bank, return_logits=True)
    solo = []
    for t, n in zip(toks, budgets):
        r = solo_eng.submit(Request(tokens=t, max_new_tokens=n))
        solo.append({c.request_id: c for c in solo_eng.drain()}[r])

    busy = fresh(model, bank, return_logits=True)
    ids = [busy.submit(Request(tokens=t, max_new_tokens=n))
           for t, n in zip(toks, budgets)]
    comps = {c.request_id: c for c in busy.drain()}
    for rid, s in zip(ids, solo):
        assert np.array_equal(comps[rid].tokens, s.tokens)
        assert np.array_equal(comps[rid].logits, s.logits)
        assert len(comps[rid].tokens) == len(s.tokens)


def test_fused_paged_engine_matches_unfused(cfg, model, bank):
    """fused=True swaps the step attention inner loop for the Pallas paged
    kernel: same tokens, BMA logits equal to the unfused engine."""
    toks, budgets = prompts_and_budgets(cfg)
    plain = fresh(model, bank, return_logits=True)
    fused = fresh(model, bank, fused=True, return_logits=True)
    ids_p = [plain.submit(Request(tokens=t, max_new_tokens=n))
             for t, n in zip(toks, budgets)]
    ids_f = [fused.submit(Request(tokens=t, max_new_tokens=n))
             for t, n in zip(toks, budgets)]
    a = {c.request_id: c for c in plain.drain()}
    b = {c.request_id: c for c in fused.drain()}
    for rp, rf in zip(ids_p, ids_f):
        assert np.array_equal(a[rp].tokens, b[rf].tokens)
        np.testing.assert_allclose(a[rp].logits, b[rf].logits, atol=1e-5)


# ---------------------------------------------------------------------------
# scheduler: admission, eviction, determinism
# ---------------------------------------------------------------------------
def test_admission_on_finish_not_batch_boundary(cfg, model, bank):
    """With 2 slots and 3 requests, the third is prefilled the moment the
    first finishes — mid-stream, while the second is still decoding."""
    eng = fresh(model, bank, num_slots=2, decode_chunk=2)
    toks, _ = prompts_and_budgets(cfg, seed=3)
    ids = [eng.submit(Request(tokens=t, max_new_tokens=n))
           for t, n in zip(toks[:3], (2, 8, 6))]
    out1 = eng.step()  # admits the first two; one chunk retires request 0
    assert [c.request_id for c in out1] == [ids[0]]
    assert eng.num_active == 2     # request 2 took the freed slot already
    assert eng.num_waiting == 0
    comps = {c.request_id: c for c in eng.drain()}
    assert set(comps) == set(ids[1:])
    # replaying each solo through an identical engine is bitwise-equal
    ref = fresh(model, bank, num_slots=2, decode_chunk=2)
    for rid, (t, n) in zip(ids, zip(toks[:3], (2, 8, 6))):
        r = ref.submit(Request(tokens=t, max_new_tokens=n))
        want = {c.request_id: c for c in ref.drain()}[r]
        got = comps.get(rid, out1[0])
        assert np.array_equal(got.tokens, want.tokens)


def test_priority_eviction_replays_victim_bitwise(cfg, model, bank):
    """A higher-priority arrival preempts the running low-priority request;
    the victim requeues and — thanks to position-folded keys — replays the
    exact same tokens it would have produced undisturbed."""
    ref = DecodeEngine(model=model, params=bank, max_seq=32)
    eng = fresh(model, bank, num_slots=1, decode_chunk=2)
    toks, _ = prompts_and_budgets(cfg, seed=7)
    tl, th = toks[0], toks[1]
    rl = eng.submit(Request(tokens=tl, max_new_tokens=8, priority=0))
    eng.step()  # low admitted, two tokens in flight
    rh = eng.submit(Request(tokens=th, max_new_tokens=4, priority=5))
    comps = {c.request_id: c for c in eng.drain()}
    cl, ch = comps[rl], comps[rh]
    # num_slots=1 keeps the step width at the contiguous B=1 shape, so the
    # strong bitwise-vs-contiguous comparison applies to both requests
    assert np.array_equal(cl.tokens, ref.generate(tl[None], 8).tokens[0])
    assert np.array_equal(ch.tokens, ref.generate(th[None], 4).tokens[0])
    assert cl.timing.get("evictions", 0) == 1
    assert "evictions" not in ch.timing or ch.timing["evictions"] == 0
    assert ch.timing["finished"] <= cl.timing["finished"]


def test_sampled_requests_deterministic_per_key_and_in_vocab(cfg, model,
                                                            bank):
    eng = fresh(model, bank)
    toks, _ = prompts_and_budgets(cfg, seed=9)
    t = toks[0]

    def run(seed):
        r = eng.submit(Request(tokens=t, max_new_tokens=8,
                               key=np.asarray(jax.random.PRNGKey(seed),
                                              np.uint32)))
        return {c.request_id: c for c in eng.drain()}[r]

    a, b, c = run(11), run(11), run(12)
    assert np.array_equal(a.tokens, b.tokens)
    assert not np.array_equal(a.tokens, c.tokens)
    assert a.tokens.min() >= 0 and a.tokens.max() < cfg.vocab_size


# ---------------------------------------------------------------------------
# trace discipline / allocator hygiene
# ---------------------------------------------------------------------------
def test_one_step_trace_plus_one_prefill_trace_per_rung(cfg, model, bank):
    """Lifetime trace budget: one prefill trace per prompt rung touched,
    ONE step trace total; a warm replay of the whole stream compiles
    nothing and allocates no pad scratch."""
    eng = fresh(model, bank, return_logits=True)
    toks, budgets = prompts_and_budgets(cfg)
    rungs = {1 << (len(t) - 1).bit_length() for t in toks}

    def stream():
        ids = [eng.submit(Request(tokens=t, max_new_tokens=n))
               for t, n in zip(toks, budgets)]
        return ids, eng.drain()

    stream()  # cold: compiles prefill rungs + the step body
    assert eng.num_traces == len(rungs) + 1
    assert eng.num_host_pad_allocs == len(rungs)
    with instrument() as rep:
        _, comps = stream()  # warm replay
    assert rep.num_traces == 0, rep.traces
    assert rep.num_pad_allocs == 0, rep.pad_allocs
    assert len(comps) == len(toks)
    assert eng.num_traces == len(rungs) + 1
    # every page is back in the pool once the stream drains
    assert eng._allocator.free_pages == eng.num_pages - 1
    assert eng.num_active == 0 and eng.num_waiting == 0


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------
def test_paged_validation_errors(cfg, model, bank):
    eng = fresh(model, bank)
    t = np.zeros((5,), np.int32)
    with pytest.raises(ValueError, match="1-D prompt"):
        eng.submit(Request(tokens=np.zeros((2, 5), np.int32),
                           max_new_tokens=3))
    with pytest.raises(ValueError, match="max_new_tokens >= 1"):
        eng.submit(Request(tokens=t, max_new_tokens=0))
    with pytest.raises(ValueError, match="overflows"):
        eng.submit(Request(tokens=t, max_new_tokens=30))  # 5 + 30 > 32
    with pytest.raises(ValueError, match="multiple of"):
        fresh(model, bank, max_seq=30)  # 30 % 8 != 0


# ---------------------------------------------------------------------------
# fused Pallas paged step vs oracle
# ---------------------------------------------------------------------------
def test_paged_kernel_bitwise_vs_ref():
    S, H, KV, hd, n_pages, ps, maxp = 3, 4, 2, 16, 7, 4, 3
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (S, H, hd), jnp.bfloat16)
    kn = jax.random.normal(ks[1], (S, KV, hd), jnp.bfloat16)
    vn = jax.random.normal(ks[2], (S, KV, hd), jnp.bfloat16)
    kp = jax.random.normal(ks[3], (n_pages, ps, KV, hd), jnp.bfloat16)
    vp = jax.random.normal(ks[4], (n_pages, ps, KV, hd), jnp.bfloat16)
    tables = jnp.asarray([[1, 4, 0], [2, 0, 0], [3, 5, 6]], jnp.int32)
    pos = jnp.asarray([5, 2, 9], jnp.int32)
    o, ko, vo = fused_paged_decode_step(q, kn, vn, kp, vp, tables, pos)
    ro, rk, rv = paged_decode_step_ref(q.reshape(S, KV, H // KV, hd), kn, vn,
                                       kp, vp, tables, pos)
    assert np.array_equal(np.asarray(o, jnp.float32),
                          np.asarray(ro.reshape(S, H, hd), jnp.float32))
    assert np.array_equal(np.asarray(ko), np.asarray(rk))
    assert np.array_equal(np.asarray(vo), np.asarray(rv))
    # each slot's new row landed in its own mapped page at pos % page_size
    for s, (p, off) in enumerate([(4, 1), (2, 2), (6, 1)]):
        assert np.array_equal(np.asarray(ko[p, off]), np.asarray(kn[s])), s


def test_paged_kernel_chain_batched_bitwise():
    """Chain axis via vmap (pallas batching rule): each chain's output must
    equal its own single-call kernel run bitwise."""
    Cc, S, H, KV, hd, n_pages, ps = 3, 2, 4, 2, 8, 5, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(ks[0], (Cc, S, H, hd), jnp.bfloat16)
    kn = jax.random.normal(ks[1], (Cc, S, KV, hd), jnp.bfloat16)
    vn = jax.random.normal(ks[2], (Cc, S, KV, hd), jnp.bfloat16)
    kp = jax.random.normal(ks[3], (Cc, n_pages, ps, KV, hd), jnp.bfloat16)
    vp = jax.random.normal(ks[4], (Cc, n_pages, ps, KV, hd), jnp.bfloat16)
    tables = jnp.asarray([[1, 3], [2, 4]], jnp.int32)
    pos = jnp.asarray([6, 3], jnp.int32)
    out = jax.vmap(lambda a, b, c, d, e: fused_paged_decode_step(
        a, b, c, d, e, tables, pos))(q, kn, vn, kp, vp)
    for c in range(Cc):
        one = fused_paged_decode_step(q[c], kn[c], vn[c], kp[c], vp[c],
                                      tables, pos)
        for got, want in zip(out, one):
            assert np.array_equal(np.asarray(got[c], jnp.float32),
                                  np.asarray(want, jnp.float32)), c


# ---------------------------------------------------------------------------
# sharded paged decode (subprocess: 8 forced host devices, debug mesh)
# ---------------------------------------------------------------------------
SCRIPT_SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.cluster import PagedDecodeEngine
from repro.cluster.api import Request
from repro.configs import get_reduced
from repro.launch.mesh import make_debug_mesh
from repro.models.transformer import Model, init_params

cfg = get_reduced("qwen3-4b")
model = Model(cfg, remat=False)
bank = jax.vmap(lambda k: init_params(k, cfg))(
    jax.random.split(jax.random.PRNGKey(0), 8))
rng = np.random.default_rng(0)
reqs = [(rng.integers(0, cfg.vocab_size, (t,), dtype=np.int32), n)
        for t, n in [(5, 6), (3, 4), (7, 5)]]

def run(**kw):
    eng = PagedDecodeEngine(model=model, params=bank, num_slots=2,
                            page_size=8, max_seq=32, decode_chunk=4,
                            return_logits=True, **kw)
    ids = [eng.submit(Request(tokens=t, max_new_tokens=n)) for t, n in reqs]
    comps = {c.request_id: c for c in eng.drain()}
    return [comps[r] for r in ids], eng

def err(xs, ys):
    return max(float(np.abs(x.logits - y.logits).max())
               for x, y in zip(xs, ys))

a, _ = run()
mesh = make_debug_mesh(data=4, model=2)
b, sharded = run(mesh=mesh)
c, _ = run(mesh=mesh, shard_params=True)
print(json.dumps({
    "tokens_equal": all(bool(np.array_equal(x.tokens, y.tokens))
                        for x, y in zip(a, b)),
    "logits_err": err(a, b),
    "chain_axis_sharded":
        jax.tree_util.tree_leaves(sharded.params)[0].sharding.spec[0]
        == "data",
    "twod_tokens_equal": all(bool(np.array_equal(x.tokens, y.tokens))
                             for x, y in zip(a, c)),
    "twod_logits_err": err(a, c),
}))
"""


@pytest.mark.slow
def test_sharded_paged_decode_matches_single_device():
    """Chain-sharded paged decode (per-token all-gather + replicated BMA)
    streams the same tokens as the single-device engine with BMA logits
    within ``LOGP_ATOL`` (a different program), and the 2-D (chains x
    tensor-parallel) bank agrees too."""
    from subproc import run_json

    res = run_json(SCRIPT_SHARDED, timeout=900)
    assert res["tokens_equal"], res
    assert res["logits_err"] <= LOGP_ATOL, res
    assert res["chain_axis_sharded"], res
    assert res["twod_tokens_equal"], res
    assert res["twod_logits_err"] <= LOGP_ATOL, res
