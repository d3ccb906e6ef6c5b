"""The reduction from profiler traces to metrics: on hand-made traces with
worked values, and on a small trace recorded on a TPU v5e checked against
a brute-force reading of the same events."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import traces  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "trace_qwen3-4b.sample.json.gz")


def hand_made():
    # window 0..100 ns; ops 10-20 and 15-35 overlap, 50-60 apart
    return {"window": [0.0, 100.0],
            "devices": {"/device:TPU:0": {
                "ops": [["fusion.1", 10.0, 10.0], ["copy.3", 15.0, 20.0],
                        ["custom-call.2", 50.0, 10.0],
                        ["fusion.1", 95.0, 10.0]],
                "modules": [["jit_chunk(1)", 5.0, 60.0]]}},
            "host": [["chipbench.window", 0.0, 100.0],
                     ["sample.run", 0.0, 60.0], ["inner", 25.0, 5.0],
                     ["after", 60.0, 40.0]]}


def test_busy_and_idle_share():
    tr = hand_made()
    # union: 10-35, 50-60, 95-100 (clipped to the window) = 40 ns
    assert traces.busy_ns(tr, "/device:TPU:0") == 40.0
    assert traces.idle_share(tr) == pytest.approx(60.0)
    assert traces.window_s(tr) == pytest.approx(1e-7)


def test_named_time_and_count():
    tr = hand_made()
    assert traces.named_ns(tr, r"^fusion") == 15.0
    assert traces.named_count(tr, r"^fusion") == 2
    assert traces.named_ns(tr, r"chunk", line="modules") == 60.0


def test_breakdown():
    tr = hand_made()
    assert traces.top_ops(tr)[0] == ["copy", pytest.approx(2e-8)]
    # idle 0-10 and 35-50 under sample.run (inner covers none of it),
    # 60-95 under after
    gaps = dict(traces.idle_gaps(tr))
    assert gaps == {"after": pytest.approx(3.5e-8),
                    "sample.run": pytest.approx(2.5e-8)}


def _brute_busy(events, lo, hi):
    cuts = sorted({lo, hi} | {t for _, s, d in events for t in (s, s + d)
                              if lo < t < hi})
    busy = 0.0
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        if any(s <= mid < s + d for _, s, d in events):
            busy += b - a
    return busy


def test_recorded_trace():
    tr = traces.read(RECORDED)
    lo, hi = tr["window"]
    for dev, d in tr["devices"].items():
        assert traces.busy_ns(tr, dev) == pytest.approx(
            _brute_busy(d["ops"], lo, hi), rel=1e-9)
    share = traces.idle_share(tr)
    assert 0.0 <= share < 100.0
    # one line's events nest, so their self times add up to the busy time
    busy = sum(traces.busy_ns(tr, dev) for dev in tr["devices"])
    assert sum(v for _, v in traces.top_ops(tr, n=10**6)) * 1e9 == \
        pytest.approx(busy / len(tr["devices"]), rel=1e-6)
    idle = sum(v for _, v in traces.idle_gaps(tr, n=10**6))
    assert idle == pytest.approx(share / 100 * traces.window_s(tr), rel=1e-6)


def test_recorded_trace_names():
    from chipbench import harness

    tr = traces.read(RECORDED)
    kernel = harness.reader("langevin_update_roofline").KERNEL
    # one fused update per parameter leaf and commit: the trace was recorded
    # with the head untied, 14 leaves
    assert traces.named_count(tr, kernel) % 14 == 0
    assert traces.named_count(tr, kernel) > 0
    assert traces.named_ns(tr, kernel) > 0
    assert traces.named_ns(tr, r"chunk", line="modules") > 0
    names = [n for n, _ in traces.top_ops(tr, n=10**6)]
    assert "tpu_custom_call" in names and "fusion" in names
    assert all(" = " not in n for n in names)
