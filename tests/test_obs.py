"""repro.obs: span tracer, metrics registry, Chrome-trace timeline export.

The observability contract: spans are host-side only (a traced warm decode
stream keeps empty ``stream_flags()`` and jaxlint stays silent on the obs
package), the exported timelines are valid Chrome-trace-event JSON with the
attributes the paper's diagnostics need (per-commit staleness, per-token
slices), and ``log_hook``'s printed format is byte-identical with the
metrics registry wired in.
"""

import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.instrument import instrument
from repro.cluster import DecodeEngine, WorkerSchedule
from repro.configs import get_reduced
from repro.models.transformer import Model, init_params
from repro.obs.metrics import (
    LATENCY_MS_BUCKETS,
    STALENESS_BUCKETS,
    Registry,
    registry,
)
from repro.obs.timeline import (
    cluster_timeline,
    decode_timeline,
    paged_timeline,
    summarize,
    validate_chrome_trace,
    write_chrome_trace,
)
from repro.obs.trace import Tracer, span, tracer
from repro.train.engine import log_hook

ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------
def test_disabled_span_is_shared_noop_context():
    tr = tracer()
    assert not tr.enabled  # global tracer starts disabled
    ctx1, ctx2 = span("a"), span("b", attr=1)
    assert ctx1 is ctx2  # one shared null context, no allocation
    with ctx1 as sp:
        sp.set(ignored=True)  # null span swallows attributes
    assert tr.spans == []


def test_spans_nest_with_parent_links_across_instrument_regions():
    tr = Tracer(enabled=True)
    with instrument():
        with tr.span("outer", level=0) as outer:
            with instrument():  # nested instrument regions don't break spans
                with tr.span("inner", level=1) as inner:
                    pass
            with tr.span("sibling") as sibling:
                pass
    spans = {sp.name: sp for sp in tr.spans}
    assert spans["inner"].parent_id == outer.span_id
    assert spans["sibling"].parent_id == outer.span_id
    assert spans["outer"].parent_id is None
    assert inner.t0 >= outer.t0 and inner.t1 <= spans["outer"].t1
    assert spans["outer"].attrs == {"level": 0}


def test_record_backfills_span_under_live_parent():
    tr = Tracer(enabled=True)
    with tr.span("chunk_loop") as parent:
        tr.record("chunk", 1.0, 2.0, start=0, end=50)
    (rec,) = [sp for sp in tr.spans if sp.name == "chunk"]
    assert rec.parent_id == parent.span_id
    assert (rec.t0, rec.t1) == (1.0, 2.0)
    assert tr.drain() and tr.spans == []  # drain clears the buffer


# ---------------------------------------------------------------------------
# profiler bridge: live spans land in the jax.profiler trace
# ---------------------------------------------------------------------------
#: the sampling executor's span names (the contract a profile is read by)
EXECUTOR_SPANS = {"cluster.run", "cluster.schedule", "engine.chunk",
                  "engine.dispatch", "engine.hooks", "engine.fetch"}


def _profiled(tmp_path, body) -> list:
    """Run ``body()`` under ``jax.profiler``; returns the host planes'
    events as ``(name, start_ns, end_ns, stats)``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name.startswith("/host")
            for line in plane.lines for e in line.events]


def _tiny_cluster_run(steps=6, chunk=2):
    from repro import samplers
    from repro.cluster import ClusterEngine
    from repro.core import Quadratic

    quad = Quadratic.make(jax.random.PRNGKey(0), d=4, m=1.0, L=3.0)
    sampler = samplers.sgld("consistent", lambda p, b: (quad.grad(p, b), 0.0),
                            has_aux=True, gamma=0.01, sigma=0.5, tau=2)
    engine = ClusterEngine(sampler, num_chains=2, chunk_size=chunk,
                           collect_aux=True, donate=False)
    state = engine.init(jnp.zeros(4), jax.random.PRNGKey(1))
    schedule = np.minimum(np.arange(steps), 2)
    engine.run(state, steps=steps, schedule=schedule)  # compile outside
    return lambda: engine.run(state, steps=steps, schedule=schedule)


def _inside(outer, inner) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_executor_spans_reach_the_profiler_host_plane(tmp_path):
    run = _tiny_cluster_run(steps=6, chunk=2)
    tr = tracer().enable()
    tr.clear()
    try:
        events = _profiled(tmp_path, run)
    finally:
        tr.disable()
        tr.clear()
    by = {n: [e for e in events if e[0] == n] for n in EXECUTOR_SPANS}
    (run_ev,), (sched,) = by["cluster.run"], by["cluster.schedule"]
    assert run_ev[3] == {"steps": 6, "chains": 2}  # attrs become stats
    assert _inside(run_ev, sched)
    chunks = sorted(by["engine.chunk"], key=lambda e: e[1])
    assert [c[3] for c in chunks] == [{"start": 0, "size": 2},
                                      {"start": 2, "size": 2},
                                      {"start": 4, "size": 2}]
    assert len(by["engine.dispatch"]) == len(by["engine.hooks"]) == 3
    for c in chunks:
        assert _inside(run_ev, c)
        assert sum(_inside(c, d) for d in by["engine.dispatch"]) == 1
        assert sum(_inside(c, h) for h in by["engine.hooks"]) == 1
    (fetch,) = by["engine.fetch"]
    assert _inside(run_ev, fetch) and fetch[1] >= chunks[-1][2]


def test_disabled_tracer_emits_no_profiler_event(tmp_path):
    run = _tiny_cluster_run(steps=4, chunk=2)
    assert not tracer().enabled
    events = _profiled(tmp_path, run)
    assert not EXECUTOR_SPANS & {e[0] for e in events}
    assert tracer().spans == []


def test_backfilled_span_stays_in_the_buffer(tmp_path):
    tr = Tracer(enabled=True)

    def body():
        with tr.span("live.outer", k=1):
            tr.record("backfilled", 0.0, 1.0)

    names = {e[0] for e in _profiled(tmp_path, body)}
    assert "live.outer" in names and "backfilled" not in names
    assert {sp.name for sp in tr.spans} == {"live.outer", "backfilled"}


def test_compiled_chunk_names_the_sampler_stages():
    """The fused W-Con chunk's compiled text carries each stage's named
    scope in its ops' metadata: the path a profile reads stages by."""
    from repro import samplers
    from repro.cluster import ClusterEngine
    from repro.core import Quadratic

    quad = Quadratic.make(jax.random.PRNGKey(0), d=4, m=1.0, L=3.0)
    sampler = samplers.sgld("consistent", lambda p, b: quad.grad(p, b),
                            gamma=0.01, sigma=0.5, tau=2, fused=True)
    engine = ClusterEngine(sampler, num_chains=1, chunk_size=2,
                           per_chain_batches=True)
    state = engine.init(jnp.zeros(4), jax.random.PRNGKey(1))
    text = engine.lower_chunk(state, jnp.zeros((2, 1, 1)),
                              {"rv": jnp.zeros((2, 1), jnp.int32)}
                              ).compile().as_text()
    # the chain vmap wraps each scope: ".../vmap(fused_update)/..."
    scopes = set(re.findall(r'op_name="jit\(chunk\)/[^"]*?[/(](delay_read|'
                            r'gradients|fused_update)[/)]', text))
    assert scopes == {"delay_read", "gradients", "fused_update"}


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_counter_is_monotone():
    reg = Registry()
    c = reg.counter("x", "help")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registry_is_idempotent_and_kind_checked():
    reg = Registry()
    assert reg.counter("a") is reg.counter("a")
    with pytest.raises(TypeError):
        reg.gauge("a")


def test_histogram_buckets_and_quantiles():
    reg = Registry()
    h = reg.histogram("lat", (1.0, 10.0, 100.0))
    h.observe_many([0.5, 5.0, 5.0, 50.0, 500.0])
    assert h.counts == [1, 2, 1, 1]  # last bucket is +inf overflow
    assert h.total == 5
    assert h.mean == pytest.approx(112.1)
    assert h.quantile(0.5) == 10.0  # conservative: bucket upper bound
    assert h.quantile(0.99) == float("inf")
    with pytest.raises(ValueError):
        reg.histogram("bad", (3.0, 1.0))


def test_snapshot_is_json_ready_and_omits_nan_gauges():
    reg = Registry()
    reg.counter("c").inc(2)
    reg.gauge("g_set").set(1.5)
    reg.gauge("g_never_set")
    reg.histogram("h", (1.0,)).observe(0.5)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert set(snap) == {"c", "g_set", "h"}  # NaN gauge dropped
    assert snap["c"] == {"type": "counter", "value": 2.0}
    assert snap["h"]["counts"] == [1, 0]


def test_prometheus_exposition_format():
    reg = Registry()
    reg.counter("decode.tokens", "tokens out").inc(7)
    h = reg.histogram("serve.request_ms", (1.0, 10.0), "latency")
    h.observe_many([0.5, 5.0, 50.0])
    text = reg.prometheus()
    assert "# TYPE decode_tokens counter\ndecode_tokens 7" in text
    assert '# HELP decode_tokens tokens out' in text
    assert 'serve_request_ms_bucket{le="1"} 1' in text
    assert 'serve_request_ms_bucket{le="10"} 2' in text  # cumulative
    assert 'serve_request_ms_bucket{le="+Inf"} 3' in text
    assert "serve_request_ms_count 3" in text


def test_write_snapshot_and_append_jsonl(tmp_path):
    reg = Registry()
    reg.counter("n").inc()
    snap = reg.write_snapshot(tmp_path / "m.json")
    assert json.loads((tmp_path / "m.json").read_text()) == snap
    reg.append_jsonl(tmp_path / "trail.jsonl", run=1)
    reg.counter("n").inc()
    reg.append_jsonl(tmp_path / "trail.jsonl", run=2)
    lines = [json.loads(ln)
             for ln in (tmp_path / "trail.jsonl").read_text().splitlines()]
    assert [ln["run"] for ln in lines] == [1, 2]
    assert lines[1]["metrics"]["n"]["value"] == 2.0


# ---------------------------------------------------------------------------
# log_hook keeps its printed format, and lands in the registry
# ---------------------------------------------------------------------------
def test_log_hook_format_byte_identical_and_metrics_recorded():
    lines = []
    hook = log_hook(every=1, log_fn=lines.append, key="loss")
    before = registry().counter("train.log_lines").value
    hook(1, None, {"loss": np.asarray([0.125])})
    assert len(lines) == 1
    # the pinned format: "step {i:5d} {key} {v:8.4f} ({t:6.1f}s)"
    assert re.fullmatch(r"step     0 loss   0\.1250 \(\s*\d+\.\ds\)",
                        lines[0])
    assert registry().counter("train.log_lines").value == before + 1
    assert registry().gauge("train.last_loss").value == 0.125


# ---------------------------------------------------------------------------
# timeline export
# ---------------------------------------------------------------------------
def _schedule():
    # 2 workers round-robin, version read 2 commits back of the newest
    k = np.arange(6)
    return WorkerSchedule(
        read_versions=np.maximum(k - 2, 0).astype(np.int32),
        worker_ids=(k % 2).astype(np.int32),
        commit_times=(0.5 + 0.5 * k).astype(np.float64),
        num_workers=2,
        batch_sizes=np.full(6, 8, np.int32))


def test_cluster_timeline_is_valid_and_carries_staleness():
    trace = cluster_timeline([_schedule(), _schedule()], max_chains=1)
    assert validate_chrome_trace(trace) == []
    commits = [ev for ev in trace["traceEvents"] if ev.get("ph") == "X"]
    assert len(commits) == 6  # max_chains dropped the second chain
    by_commit = {ev["args"]["commit"]: ev for ev in commits}
    assert by_commit[5]["args"]["staleness"] == 2
    assert by_commit[5]["args"]["read_version"] == 3
    assert by_commit[5]["args"]["batch_size"] == 8
    # worker 1's commit 5 starts at its own previous commit (k=3, t=2.0)
    assert by_commit[5]["tid"] == 1
    assert by_commit[5]["ts"] == pytest.approx(2.0e6)
    assert by_commit[5]["dur"] == pytest.approx(1.0e6)


def test_decode_timeline_amortizes_token_slices():
    spans = [{"name": "decode.generate", "id": 7, "parent": None,
              "t0": 1.0, "t1": 2.0, "tid": 123,
              "attrs": {"B": 3, "T": 5, "b_rung": 4, "t_rung": 8,
                        "new_tokens": 2, "chains": 4}}]
    trace = decode_timeline(spans)
    assert validate_chrome_trace(trace) == []
    evs = [ev for ev in trace["traceEvents"] if ev.get("ph") == "X"]
    names = [ev["name"] for ev in evs]
    assert names == ["decode.generate", "decode.prefill", "decode.token",
                     "decode.token"]
    # 1s split over t_rung + new_tokens = 10 position units
    unit_us = 1e6 / 10
    assert evs[1]["dur"] == pytest.approx(8 * unit_us)  # prefill: 8 cached
    assert evs[2]["dur"] == pytest.approx(unit_us)
    assert evs[3]["ts"] == pytest.approx(evs[2]["ts"] + evs[2]["dur"])
    assert all(ev["args"]["amortized"] for ev in evs[1:])
    assert all(ev["args"]["request_span"] == 7 for ev in evs[1:])


def test_paged_timeline_per_slot_rows_and_queue_wait():
    """Slot rows carry prefill + residency, the queue wait is derived from
    submission to the *first* admit (an evicted request admits twice), and
    decode chunks land on the scheduler row."""
    spans = [
        {"name": "paged.admit", "id": 1, "parent": None, "t0": 1.0,
         "t1": 1.2, "tid": 9,
         "attrs": {"slot": 0, "request_id": 41, "T": 5, "t_rung": 8,
                   "pages": 2}},
        # request 41 was evicted and re-admitted later on slot 1
        {"name": "paged.admit", "id": 2, "parent": None, "t0": 2.0,
         "t1": 2.1, "tid": 9,
         "attrs": {"slot": 1, "request_id": 41, "T": 5, "t_rung": 8,
                   "pages": 2}},
        {"name": "paged.decode_chunk", "id": 3, "parent": None, "t0": 1.2,
         "t1": 1.5, "tid": 9, "attrs": {"active": 2, "chunk": 4}},
        {"name": "paged.request", "id": 4, "parent": None, "t0": 0.5,
         "t1": 2.5, "tid": 9,
         "attrs": {"slot": 1, "request_id": 41, "new_tokens": 6,
                   "evictions": 1}},
    ]
    trace = paged_timeline(spans)
    assert validate_chrome_trace(trace) == []
    evs = {ev["name"]: ev for ev in trace["traceEvents"]
           if ev.get("ph") == "X"}
    # wait slice: submission (0.5) until the FIRST prefill start (1.0),
    # rendered on the first admitting slot's row
    assert evs["paged.wait"]["ts"] == pytest.approx(0.5e6)
    assert evs["paged.wait"]["dur"] == pytest.approx(0.5e6)
    assert evs["paged.wait"]["tid"] == 0
    assert evs["paged.request"]["tid"] == 1  # finished on slot 1
    assert evs["paged.request"]["args"]["evictions"] == 1
    # scheduler row sits above the highest slot row
    assert evs["paged.decode_chunk"]["tid"] == 2
    names = {(ev["pid"], ev.get("tid")): ev["args"]["name"]
             for ev in trace["traceEvents"] if ev.get("ph") == "M"}
    assert names[(0, 0)] == "slot 0"
    assert names[(0, 1)] == "slot 1"
    assert names[(0, 2)] == "scheduler"


def test_paged_timeline_from_live_engine():
    """The spans a real PagedDecodeEngine records export to a valid
    timeline with one admit per (admission incl. eviction replays) and one
    residency per completed request."""
    from repro.cluster import PagedDecodeEngine
    from repro.cluster.api import Request

    cfg = get_reduced("qwen3-4b")
    model = Model(cfg, remat=False)
    bank = jax.vmap(lambda k: init_params(k, cfg))(
        jax.random.split(jax.random.PRNGKey(0), 2))
    tr = tracer().enable()
    tr.clear()
    try:
        eng = PagedDecodeEngine(model=model, params=bank, num_slots=2,
                                page_size=8, max_seq=32, decode_chunk=4)
        rng = np.random.default_rng(0)
        for t, n in [(5, 4), (3, 2), (6, 5)]:
            eng.submit(Request(
                tokens=rng.integers(0, cfg.vocab_size, (t,),
                                    dtype=np.int32), max_new_tokens=n))
        comps = eng.drain()
        trace = paged_timeline(tr.drain())
    finally:
        tr.disable()
    assert validate_chrome_trace(trace) == []
    evs = [ev for ev in trace["traceEvents"] if ev.get("ph") == "X"]
    by = lambda n: [ev for ev in evs if ev["name"] == n]  # noqa: E731
    assert len(by("paged.request")) == len(comps) == 3
    assert len(by("paged.admit")) == 3  # no evictions in this stream
    assert len(by("paged.wait")) == 3
    assert len(by("paged.decode_chunk")) >= 1
    assert {ev["args"]["new_tokens"] for ev in by("paged.request")} \
        == {4, 2, 5}


def test_to_chrome_trace_and_summarize_roundtrip(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("a", k=1):
        with tr.span("b"):
            pass
    trace = write_chrome_trace(tmp_path / "t.json", tr.spans)
    assert validate_chrome_trace(trace) == []
    reread = json.loads((tmp_path / "t.json").read_text())
    assert reread == trace
    s = summarize(reread)
    assert s["makespan_s"] > 0 and s["critical"] is not None
    with pytest.raises(ValueError):
        write_chrome_trace(tmp_path / "bad.json", {"not_a_trace": 1})


def test_summarize_staleness_histogram():
    s = summarize(cluster_timeline(_schedule()))
    # delays of the fixture: k - max(k - 2, 0) = [0, 1, 2, 2, 2, 2]
    assert s["staleness_hist"] == {0: 1, 1: 1, 2: 4}


# ---------------------------------------------------------------------------
# traced warm decode stream: tracing is host-side only
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_traced_warm_decode_stream_keeps_stream_flags_empty():
    cfg = get_reduced("qwen3-4b")
    model = Model(cfg, remat=False)
    bank = jax.vmap(lambda k: init_params(k, cfg))(
        jax.random.split(jax.random.PRNGKey(0), 2))
    eng = DecodeEngine(model=model, params=bank, max_seq=32)
    prompt = np.zeros((2, 4), np.int32)
    eng.generate(prompt, 3)  # warm the (rung, max_new) trace
    tr = tracer()
    tr.clear()
    tr.enable()
    try:
        with instrument() as rep:
            for _ in range(3):
                eng.generate(prompt, 3)
    finally:
        tr.disable()
    # the tentpole invariant: tracing adds no retrace / pad alloc
    assert rep.stream_flags() == {"retraced_in_stream": False,
                                  "pad_allocs_in_stream": 0}
    spans = [sp for sp in tr.drain() if sp.name == "decode.generate"]
    assert len(spans) == 3
    assert spans[0].attrs["new_tokens"] == 3
    trace = decode_timeline(spans)
    assert validate_chrome_trace(trace) == []
    assert sum(ev["name"] == "decode.token"
               for ev in trace["traceEvents"]) == 9


def test_decode_metrics_land_in_registry():
    before = registry().counter("decode.requests").value
    cfg = get_reduced("qwen3-4b")
    bank = jax.vmap(lambda k: init_params(k, cfg))(
        jax.random.split(jax.random.PRNGKey(1), 2))
    eng = DecodeEngine(model=Model(cfg, remat=False), params=bank, max_seq=32)
    eng.generate(np.zeros((2, 4), np.int32), 2)
    assert registry().counter("decode.requests").value == before + 1
    assert registry().gauge("decode.bank_rungs").value >= 1.0
    assert registry().histogram(
        "decode.per_token_ms", LATENCY_MS_BUCKETS).total >= 1


# ---------------------------------------------------------------------------
# lint: the obs package (and everything that imports it) stays jaxlint-clean
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_jaxlint_silent_on_obs_and_benchmarks():
    # the CI lint job's exact command; obs spans must not introduce JL004
    # host-sync sites or any other finding into the linted tree
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "jaxlint.py"),
         os.path.join(ROOT, "src"), os.path.join(ROOT, "benchmarks")],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# obstool CLI
# ---------------------------------------------------------------------------
def test_obstool_cli_smoke(tmp_path, capsys):
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    try:
        import obstool
    finally:
        sys.path.pop(0)
    write_chrome_trace(tmp_path / "t.json", cluster_timeline(_schedule()))
    reg = Registry()
    reg.counter("cluster.commits", "").inc(6)
    reg.histogram("lat", (1.0, 10.0)).observe_many([0.5, 5.0])
    reg.write_snapshot(tmp_path / "m.json")
    rc = obstool.main([str(tmp_path / "t.json"),
                       "--metrics", str(tmp_path / "m.json")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "critical path" in out and "staleness over commit spans" in out
    assert "cluster.commits" in out and "p99<=10" in out
    # an invalid timeline is reported and exits non-zero
    (tmp_path / "bad.json").write_text('{"traceEvents": [{"ph": "Z"}]}')
    assert obstool.main([str(tmp_path / "bad.json")]) == 1


def test_staleness_buckets_cover_ring_depths():
    # tau=0 (synchronous) must be distinguishable from tau>=1
    assert STALENESS_BUCKETS[0] == 0 and STALENESS_BUCKETS[1] == 1
