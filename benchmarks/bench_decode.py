"""Streaming BMA decode from the chain bank: tokens/sec and per-token
latency percentiles vs. chain count and shard count.

A :class:`~repro.cluster.decode.DecodeEngine` streams greedy generations for
a mixed prompt stream (batch sizes and prompt lengths drawn from ladders, so
the (bucket, max_new) traces are genuinely exercised) against a reduced
transformer bank.  Each row reports end-to-end tokens/sec, per-token latency
percentiles, the trace count, and the prompt-scratch allocation count — the
run **fails** on an in-stream retrace, on per-request pad allocations, or
(with >= 8 devices) when sharded C=8 decoding is not sublinear in C, i.e.
when it fails to beat 8x the C=1 per-token cost.  The shard sweep runs on
whatever devices exist; CI forces 8 host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

A final **continuous-batching** block replays one Poisson arrival stream of
mixed-budget requests through a convoyed static-batch baseline (legacy
``generate``, groups of ``num_slots`` at the group-max budget) and through
:class:`~repro.cluster.paged.PagedDecodeEngine` (slot-level admission over
the paged KV bank), reporting sustained QPS, p99 TTFT, and bank-page
utilization — the run fails unless continuous batching sustains a QPS
uplift > 1 with zero in-stream retraces and zero host pad allocations.

``python benchmarks/bench_decode.py [--smoke] [--out BENCH_decode.json]``
"""

from __future__ import annotations

import argparse
import json
import time
from dataclasses import replace

import jax
import numpy as np

from repro.analysis import instrument
from repro.cluster import DecodeEngine, PagedDecodeEngine
from repro.cluster.api import (
    Request,
    STATUS_OK,
    STATUS_SHED,
    STATUS_TIMEOUT,
)
from repro.configs import get_reduced
from repro.launch.mesh import make_data_mesh
from repro.models.transformer import Model, init_params
from repro.obs import (
    decode_timeline,
    paged_timeline,
    registry,
    write_chrome_trace,
)
from repro.obs.trace import tracer
from repro.utils import bucket_size

ARCH = "qwen3-4b"


def _bench_cfg():
    """The reduced config scaled up until per-chain compute dominates
    dispatch: at the CPU-smoke size (d=256) the per-token cost is
    overhead-bound and the sharded-sublinearity margin is within CI noise;
    at d=512 the margin is a robust ~1.7x."""
    return replace(get_reduced(ARCH), d_model=512, d_ff=1536, num_heads=8,
                   num_kv_heads=2, head_dim=64, vocab_size=2048)


def _bank(cfg, chains: int, seed: int):
    return jax.vmap(lambda k: init_params(k, cfg))(
        jax.random.split(jax.random.PRNGKey(seed), chains))


def _measure(engine: DecodeEngine, *, requests: int, max_batch: int,
             max_prompt: int, max_new: int, seed: int) -> dict:
    cfg = engine.model.cfg
    rng = np.random.default_rng(seed)
    shapes = list(zip(rng.integers(1, max_batch + 1, size=requests),
                      rng.integers(4, max_prompt + 1, size=requests)))
    stream = [rng.integers(0, cfg.vocab_size, size=(int(b), int(t)),
                           dtype=np.int32) for b, t in shapes]
    rungs = sorted({(bucket_size(int(b)), bucket_size(int(t)))
                    for b, t in shapes})
    for b, t in rungs:  # compile every (bucket, max_new) pair off the clock
        engine.generate(np.zeros((b, t), np.int32), max_new)

    lat = []
    n_tokens = 0
    t_all = time.time()
    # any trace or pad alloc inside this block is a stream-path regression;
    # the report's stream_flags() feed the row fields check_bench gates on
    with instrument() as rep:
        for prompt in stream:
            t0 = time.time()
            res = engine.generate(prompt, max_new)
            lat.append(time.time() - t0)
            n_tokens += res.tokens.size
    total_s = time.time() - t_all
    per_tok_ms = np.asarray(lat) * 1e3 / max_new
    p50, p99 = (float(np.percentile(per_tok_ms, p)) for p in (50, 99))
    return {
        "chains": engine.num_chains,
        "shards": (engine.mesh.shape[engine.chain_axis]
                   if engine.mesh is not None else 1),
        "requests": requests,
        "tokens": n_tokens,
        "rungs": len(rungs),
        "traces": engine.num_traces,
        **rep.stream_flags(),
        "tokens_per_s": round(n_tokens / total_s, 1),
        "per_token_p50_ms": round(p50, 3),
        "per_token_p99_ms": round(p99, 3),
    }


def _measure_continuous(model, params, *, requests: int, num_slots: int,
                        prompt_len: int, max_new: int, max_seq: int,
                        page_size: int, decode_chunk: int,
                        arrival_qps: float, seed: int) -> dict:
    """Continuous batching vs a convoyed static batch on one Poisson
    arrival stream.

    Both servers see the same mixed-budget request stream with exponential
    inter-arrival gaps.  Arrivals live on a *virtual* clock; each service
    call's wall-clock duration advances it, so the comparison measures the
    servers, not the random sleeps.  The static baseline convoys: it groups
    ``num_slots`` requests in arrival order, waits for the group's last
    arrival, and runs one legacy batch ``generate`` at the group's pow2-
    bucketed max budget — every sequence decodes to the longest budget in
    its convoy.  The paged engine admits each request the moment a slot
    frees and retires it at its own budget.  Sustained QPS (completed
    requests over makespan) and p99 TTFT (static: batch completion; paged:
    the admission prefill that emits the first token) are reported per
    server; the uplift is the acceptance criterion.
    """
    cfg = model.cfg
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=(prompt_len,),
                            dtype=np.int32) for _ in range(requests)]
    budgets = rng.integers(2, max_new + 1, size=requests)
    arrivals = np.cumsum(rng.exponential(1.0 / arrival_qps, size=requests))

    def pow2(n):  # static budget bucket: pow2 (one trace per bucket),
        # capped at what the contiguous cache can hold past the prompt
        return min(1 << (int(n) - 1).bit_length(), max_seq - prompt_len)

    # ---- convoyed static baseline --------------------------------------
    groups = [list(range(g, min(g + num_slots, requests)))
              for g in range(0, requests, num_slots)]
    eng = DecodeEngine(model=model, params=params, max_seq=max_seq)
    for idx in groups:  # compile every (b_rung, max_new bucket) off-clock
        eng.generate(np.zeros((len(idx), prompt_len), np.int32),
                     pow2(max(budgets[i] for i in idx)))
    clock, done, generated = 0.0, {}, 0
    with instrument() as rep_s:
        for idx in groups:
            batch = np.stack([prompts[i] for i in idx])
            mn = pow2(max(budgets[i] for i in idx))
            clock = max(clock, float(arrivals[idx[-1]]))  # convoy wait
            t0 = time.time()
            eng.generate(batch, mn)
            clock += time.time() - t0
            generated += len(idx) * mn
            for i in idx:
                done[i] = clock
    useful = int(budgets.sum())
    ttft_s = [done[i] - float(arrivals[i]) for i in range(requests)]
    static = {
        "qps": round(requests / clock, 2),
        "p99_ttft_ms": round(float(np.percentile(ttft_s, 99)) * 1e3, 1),
        "makespan_s": round(clock, 4),
        "wasted_token_frac": round(1.0 - useful / generated, 4),
        **rep_s.stream_flags(),
    }

    # ---- continuous batching over the paged bank -----------------------
    peng = PagedDecodeEngine(model=model, params=params,
                             num_slots=num_slots, page_size=page_size,
                             max_seq=max_seq, decode_chunk=decode_chunk)
    for _ in range(num_slots):  # warm the prefill rung + the step body
        peng.submit(Request(tokens=prompts[0], max_new_tokens=max_new))
    peng.drain()
    traces_warm = peng.num_traces
    reqs = [Request(tokens=prompts[i], max_new_tokens=int(budgets[i]))
            for i in range(requests)]
    clock, i, n_done = 0.0, 0, 0
    windows, util = [], []
    gauge = registry().get("paged.page_utilization")
    with instrument() as rep_c:
        while n_done < requests:
            while i < requests and float(arrivals[i]) <= clock:
                peng.submit(reqs[i])
                i += 1
            if peng.num_active == 0 and peng.num_waiting == 0 \
                    and not peng._pending and i < requests:
                clock = float(arrivals[i])  # idle: fast-forward to arrival
                continue
            t0 = time.time()
            comps = peng.step()
            t1 = time.time()
            windows.append((t0, t1, clock))
            clock += t1 - t0
            n_done += len(comps)
            util.append(gauge.value)

    def virtual(wall):  # wall stamp inside a step window -> virtual clock
        for w0, w1, v0 in windows:
            if w0 <= wall <= w1:
                return v0 + (wall - w0)
        return clock

    ttft_c = [virtual(r.timing["first_token"]) - float(arrivals[j])
              for j, r in enumerate(reqs)]
    paged = {
        "qps": round(requests / clock, 2),
        "p99_ttft_ms": round(float(np.percentile(ttft_c, 99)) * 1e3, 1),
        "makespan_s": round(clock, 4),
        "page_utilization_mean": round(float(np.mean(util)), 4),
        "traces": peng.num_traces,
        "new_traces_in_stream": peng.num_traces - traces_warm,
        **rep_c.stream_flags(),
    }
    uplift = round(paged["qps"] / static["qps"], 3)
    return {
        "config": {"requests": requests, "num_slots": num_slots,
                   "prompt_len": prompt_len, "max_new": max_new,
                   "page_size": page_size, "decode_chunk": decode_chunk,
                   "arrival_qps": arrival_qps, "seed": seed},
        "static": static,
        "paged": paged,
        "qps_uplift": uplift,
        "pass": uplift > 1.0,
    }


def _measure_deadline(model, params, *, requests: int, num_slots: int,
                      prompt_len: int, max_new: int, max_seq: int,
                      page_size: int, decode_chunk: int, seed: int) -> dict:
    """Deadline-aware shedding under burst overload: goodput of a
    deadline-armed paged server vs the same server with no deadlines.

    All ``requests`` arrive at once into ``num_slots`` slots — an overload
    spike where queueing delay, not service time, dominates the tail.  The
    no-deadline arm serves the whole backlog; a request counts toward
    *goodput* only if it finished within the budget D of its submission.
    D self-calibrates to the median completion latency of that arm, so the
    comparison tracks this machine's service rate instead of hard-coding a
    wall-clock number.  The deadline arm resubmits the identical burst with
    ``deadline_ms=D``: requests past D while still waiting are shed
    un-admitted (``STATUS_SHED``, zero wasted decode) and active ones are
    cut short with their partial prefix (``STATUS_TIMEOUT``), so no slot
    keeps burning on a request that already missed its budget.  Acceptance:
    on-time completions per second of server busy time must go *up* when
    shedding is on (``goodput_uplift > 1``), every request must come back
    with a terminal status, and neither arm may trace inside the stream —
    deadline handling is host-side bookkeeping, never a recompile.
    """
    cfg = model.cfg
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=(prompt_len,),
                            dtype=np.int32) for _ in range(requests)]
    budgets = rng.integers(max(2, max_new // 2), max_new + 1, size=requests)

    def serve(deadline_ms):
        peng = PagedDecodeEngine(model=model, params=params,
                                 num_slots=num_slots, page_size=page_size,
                                 max_seq=max_seq, decode_chunk=decode_chunk)
        peng.submit(Request(tokens=prompts[0], max_new_tokens=max_new))
        peng.drain()  # warm the prefill rung + the step body off the clock
        warm = peng.num_traces
        reqs = [Request(tokens=prompts[i], max_new_tokens=int(budgets[i]),
                        deadline_ms=deadline_ms) for i in range(requests)]
        t0 = time.time()
        with instrument() as rep:
            for r in reqs:
                peng.submit(r)
            comps = peng.drain()
        makespan = time.time() - t0
        lat = [r.timing["finished"] - r.timing["submitted"] for r in reqs]
        return comps, lat, makespan, rep.stream_flags(), \
            peng.num_traces - warm

    comps0, lat0, span0, flags0, new_tr0 = serve(None)
    deadline_ms = round(float(np.percentile(lat0, 50)) * 1e3, 3)
    comps1, _, span1, flags1, new_tr1 = serve(deadline_ms)

    n_status = lambda cs, st: sum(c.status == st for c in cs)  # noqa: E731
    on_time0 = sum(lt <= deadline_ms * 1e-3 for lt in lat0)
    ok1 = n_status(comps1, STATUS_OK)
    shed1 = n_status(comps1, STATUS_SHED)
    timeout1 = n_status(comps1, STATUS_TIMEOUT)
    goodput0 = on_time0 / span0
    goodput1 = ok1 / span1
    uplift = round(goodput1 / goodput0, 3) if goodput0 else None
    return {
        "config": {"requests": requests, "num_slots": num_slots,
                   "prompt_len": prompt_len, "max_new": max_new,
                   "max_seq": max_seq, "page_size": page_size,
                   "decode_chunk": decode_chunk, "seed": seed},
        "deadline_ms": deadline_ms,
        "no_deadline": {"makespan_s": round(span0, 4),
                        "completed": len(comps0), "on_time": int(on_time0),
                        "goodput_rps": round(goodput0, 2),
                        "new_traces_in_stream": new_tr0, **flags0},
        "deadline": {"makespan_s": round(span1, 4), "ok": ok1,
                     "shed": shed1, "timeout": timeout1,
                     "goodput_rps": round(goodput1, 2),
                     "new_traces_in_stream": new_tr1, **flags1},
        "goodput_uplift": uplift,
        "pass": (uplift is not None and uplift > 1.0
                 and ok1 + shed1 + timeout1 == requests),
    }


def run(chain_sweep=(1, 4, 8), shard_sweep=(4, 8), requests: int = 40,
        max_batch: int = 8, max_prompt: int = 16, max_new: int = 16,
        max_seq: int = 64, seed: int = 0,
        continuous_kw: dict | None = None,
        deadline_kw: dict | None = None) -> dict:
    cfg = _bench_cfg()
    model = Model(cfg, remat=False)
    kw = dict(requests=requests, max_batch=max_batch, max_prompt=max_prompt,
              max_new=max_new, seed=seed + 1)
    rows = []
    # span tracing stays ON through the measured streams: the stream-flag
    # gates double as the proof that tracing adds no retrace/pad-alloc
    tr = tracer()
    tr.clear()
    tr.enable()
    try:
        for chains in chain_sweep:
            eng = DecodeEngine(model=model, params=_bank(cfg, chains, seed),
                               max_seq=max_seq)
            rows.append(_measure(eng, **kw))
        chains = max(chain_sweep)
        n_dev = len(jax.devices())
        for shards in shard_sweep:
            if shards > n_dev or chains % shards:
                continue
            mesh = make_data_mesh(shards)
            eng = DecodeEngine(model=model, params=_bank(cfg, chains, seed),
                               max_seq=max_seq, mesh=mesh)
            rows.append(_measure(eng, **kw))
    finally:
        tr.disable()
    timeline = decode_timeline(tr.drain())

    # continuous batching vs convoyed static batch, same Poisson stream.
    # Long budgets on a wide slot (max_seq 128 >> the rows' max_seq) are
    # deliberate: they grow both the convoy's pow2 over-generation and the
    # decode/prefill ratio, which is where slot-level admission pays —
    # short-budget streams are dispatch-bound and show no uplift on CPU.
    cont_kw = dict(requests=12, num_slots=4, prompt_len=4, max_new=96,
                   max_seq=128, page_size=8, decode_chunk=8,
                   arrival_qps=200.0, seed=seed + 2)
    cont_kw.update(continuous_kw or {})
    tr.enable()
    try:
        continuous = _measure_continuous(
            model, _bank(cfg, max(chain_sweep), seed), **cont_kw)
    finally:
        tr.disable()
    paged_tl = paged_timeline(tr.drain())

    # deadline-aware shedding on the same paged engine: burst overload,
    # self-calibrating budget (see _measure_deadline)
    dl_kw = dict(requests=16, num_slots=4, prompt_len=4, max_new=64,
                 max_seq=128, page_size=8, decode_chunk=8, seed=seed + 3)
    dl_kw.update(deadline_kw or {})
    deadline = _measure_deadline(model, _bank(cfg, max(chain_sweep), seed),
                                 **dl_kw)

    # acceptance: sharded C-chain decode is sublinear in C — C=8 over 8
    # devices must beat 8x the C=1 per-token cost
    sublinear = None
    c1 = next((r for r in rows if r["chains"] == 1 and r["shards"] == 1), None)
    cmax = next((r for r in rows if r["chains"] == chains
                 and r["shards"] == chains), None)
    if c1 is not None and cmax is not None:
        bound = chains * c1["per_token_p50_ms"]
        sublinear = {
            "chains": chains,
            "c1_per_token_ms": c1["per_token_p50_ms"],
            "sharded_per_token_ms": cmax["per_token_p50_ms"],
            "linear_bound_ms": round(bound, 3),
            "speedup_vs_linear": round(bound / cmax["per_token_p50_ms"], 2),
            "pass": cmax["per_token_p50_ms"] < bound,
        }
    return {
        "kind": "decode",
        "config": {"arch": ARCH, "chain_sweep": list(chain_sweep),
                   "requests": requests, "max_batch": max_batch,
                   "max_prompt": max_prompt, "max_new": max_new,
                   "max_seq": max_seq, "seed": seed,
                   "devices": n_dev},
        "rows": rows,
        "sublinear": sublinear,
        "continuous": continuous,
        "deadline": deadline,
        # per-request decode.generate spans with amortized token slices
        # (popped into <out>.timeline.json before the payload is written)
        "timeline": timeline,
        # per-slot continuous-batching timeline (<out>.paged_timeline.json)
        "paged_timeline": paged_tl,
    }


def _row(result: dict) -> dict:
    """CSV row for benchmarks.run: the largest unsharded configuration."""
    best = [r for r in result["rows"] if r["shards"] == 1][-1]
    return {
        "bench": "decode",
        "us_per_call": round(best["per_token_p50_ms"] * 1e3, 1),
        "chains": best["chains"], "tokens_per_s": best["tokens_per_s"],
        "per_token_p50_ms": best["per_token_p50_ms"],
        "per_token_p99_ms": best["per_token_p99_ms"],
        "traces": best["traces"],
        "cont_qps_uplift": result["continuous"]["qps_uplift"],
        "deadline_goodput_uplift": result["deadline"]["goodput_uplift"],
    }


SMOKE_KW = dict(chain_sweep=(1, 8), shard_sweep=(8,), requests=12,
                max_batch=4, max_prompt=8, max_new=8, max_seq=32,
                deadline_kw=dict(requests=10, max_new=32, max_seq=64))


def main(fast: bool = True):
    return [_row(run(**(SMOKE_KW if fast else {})))]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (1/8 chains, 12 requests)")
    ap.add_argument("--out", default="BENCH_decode.json")
    args = ap.parse_args()
    result = run(**(SMOKE_KW if args.smoke else {}))
    stem = args.out[:-5] if args.out.endswith(".json") else args.out
    write_chrome_trace(f"{stem}.timeline.json", result.pop("timeline"))
    write_chrome_trace(f"{stem}.paged_timeline.json",
                       result.pop("paged_timeline"))
    registry().write_snapshot(f"{stem}.metrics.json")
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(_row(result)))
    for r in result["rows"]:
        print(f"  chains={r['chains']:3d} shards={r['shards']} "
              f"tok/s={r['tokens_per_s']:9.1f} "
              f"per-tok p50={r['per_token_p50_ms']:.2f}ms "
              f"p99={r['per_token_p99_ms']:.2f}ms traces={r['traces']}")
    sub = result["sublinear"]
    if sub is not None:
        print(f"  sublinear: C={sub['chains']} sharded "
              f"{sub['sharded_per_token_ms']:.2f}ms/tok vs linear bound "
              f"{sub['linear_bound_ms']:.2f}ms ({sub['speedup_vs_linear']}x)")
    cont = result["continuous"]
    print(f"  continuous: paged {cont['paged']['qps']} qps "
          f"(p99 TTFT {cont['paged']['p99_ttft_ms']}ms, "
          f"pages {cont['paged']['page_utilization_mean']:.0%}) vs convoyed "
          f"{cont['static']['qps']} qps "
          f"(p99 TTFT {cont['static']['p99_ttft_ms']}ms, "
          f"{cont['static']['wasted_token_frac']:.0%} tokens wasted): "
          f"{cont['qps_uplift']}x uplift")
    dl = result["deadline"]
    print(f"  deadline: D={dl['deadline_ms']:.0f}ms burst of "
          f"{dl['config']['requests']}: no-deadline "
          f"{dl['no_deadline']['on_time']} on time in "
          f"{dl['no_deadline']['makespan_s']:.2f}s "
          f"({dl['no_deadline']['goodput_rps']} rps) vs shedding "
          f"{dl['deadline']['ok']} ok / {dl['deadline']['shed']} shed / "
          f"{dl['deadline']['timeout']} cut in "
          f"{dl['deadline']['makespan_s']:.2f}s "
          f"({dl['deadline']['goodput_rps']} rps): "
          f"{dl['goodput_uplift']}x goodput")
    print(f"wrote {args.out} (+ .timeline.json, .paged_timeline.json, "
          ".metrics.json)")
    if any(r["retraced_in_stream"] for r in result["rows"]):
        raise SystemExit("decode path retraced inside the prompt stream "
                         "(more than one trace per (bucket, max_new) pair)")
    if any(r["traces"] != r["rungs"] for r in result["rows"]):
        raise SystemExit("trace count != rung count: the decode program is "
                         "not exactly one trace per (bucket, max_new) pair")
    if any(r["pad_allocs_in_stream"] for r in result["rows"]):
        raise SystemExit("prompt padding allocated per request instead of "
                         "reusing the per-rung scratch")
    if sub is not None and not sub["pass"]:
        raise SystemExit(
            f"sharded decode is not sublinear in C: "
            f"{sub['sharded_per_token_ms']:.2f}ms/token >= "
            f"{sub['linear_bound_ms']:.2f}ms (C x the C=1 cost)")
    if not cont["pass"]:
        raise SystemExit(
            f"continuous batching lost its sustained-QPS uplift over the "
            f"convoyed static batch: {cont['qps_uplift']}x <= 1")
    if cont["paged"]["new_traces_in_stream"] or \
            cont["paged"]["retraced_in_stream"]:
        raise SystemExit("paged engine retraced inside the arrival stream")
    if cont["paged"]["pad_allocs_in_stream"] or \
            cont["static"]["pad_allocs_in_stream"]:
        raise SystemExit("host pad scratch allocated inside the arrival "
                         "stream instead of reusing the per-rung buffer")
    if not dl["pass"]:
        raise SystemExit(
            "deadline shedding did not raise goodput under burst overload "
            f"({dl['goodput_uplift']}x <= 1, or a request came back "
            "without a terminal status)")
    if dl["deadline"]["new_traces_in_stream"] or \
            dl["no_deadline"]["new_traces_in_stream"]:
        raise SystemExit("paged engine retraced inside the deadline burst "
                         "(deadline handling must stay host-side)")
