"""From a profiler trace to metrics.

A run with ``--trace 1`` records its window with ``jax.profiler`` and the
benchmark's own host spans (``jax.profiler.TraceAnnotation``).  ``load``
turns the ``.xplane.pb`` into a compact dict, the only form the reductions
read (and the form of the small recorded trace the tests use):

    {"window": [t0_ns, t1_ns],
     "devices": {"/device:TPU:0": {"ops": [[name, start_ns, dur_ns], ...],
                                   "modules": [[name, start_ns, dur_ns]]}},
     "host": [[name, start_ns, dur_ns], ...]}

``ops`` are the device's operations (line "XLA Ops"), ``modules`` its
compiled programs (line "XLA Modules"), ``host`` the benchmark's spans.
The window is the host span :data:`WINDOW`.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import shutil
import tempfile
from collections import defaultdict
from contextlib import contextmanager

WINDOW = "chipbench.window"
#: the benchmark's host spans, by which idle gaps are attributed
HOST_SPANS = ("sample.run", WINDOW)


def profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


@contextmanager
def recording(enabled: bool):
    """Record the body with ``jax.profiler`` when ``enabled``; the yielded
    dict holds the compact trace under ``"trace"`` once the body is done.
    The raw trace goes to a temporary directory and is removed."""
    box: dict = {}
    if not enabled:
        yield box
        return
    import jax

    logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(logdir, profiler_options=profiler_options())
        try:
            yield box
        finally:
            jax.profiler.stop_trace()
        box["trace"] = load(logdir)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def load(logdir: str) -> dict:
    """The compact form of the one trace under ``logdir``."""
    import jax

    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, got {files}")
    data = jax.profiler.ProfileData.from_file(files[0])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU") and "Core" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            dev = {}
            for key, line in (("ops", "XLA Ops"), ("modules", "XLA Modules")):
                ln = lines.get(line)
                dev[key] = [] if ln is None else [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in ln.events]
            devices[plane.name] = dev
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events if e.name in HOST_SPANS)
    win = [h for h in host if h[0] == WINDOW]
    if len(win) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, got {len(win)}")
    t0 = win[0][1]
    return {"window": [t0, t0 + win[0][2]], "devices": devices,
            "host": sorted(host, key=lambda h: h[1])}


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def clip(events, lo: float, hi: float):
    """``[start, end]`` intervals of ``events`` clipped to ``[lo, hi]``."""
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append([a, b])
    return out


def union(intervals):
    """Merged, sorted intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def busy_ns(trace: dict, device: str) -> float:
    """Nanoseconds of the window in which an operation ran on ``device``."""
    lo, hi = trace["window"]
    return length(union(clip(trace["devices"][device]["ops"], lo, hi)))


def busy_s_mean(trace: dict) -> float:
    """Busy seconds averaged over the traced devices."""
    devs = list(trace["devices"])
    return sum(busy_ns(trace, d) for d in devs) / len(devs) / 1e9


def window_s(trace: dict) -> float:
    lo, hi = trace["window"]
    return (hi - lo) / 1e9


def idle_share(trace: dict) -> float:
    """1 - busy over the window, averaged over devices, in percent."""
    return 100.0 * (1.0 - busy_s_mean(trace) / window_s(trace))


def named_ns(trace: dict, pattern: str, line: str = "ops") -> float:
    """Summed device time of the events whose name matches ``pattern``
    (a regular expression), over all devices, inside the window."""
    lo, hi = trace["window"]
    rx = re.compile(pattern)
    return sum(length(clip([e for e in dev[line] if rx.search(e[0])], lo, hi))
               for dev in trace["devices"].values())


def named_count(trace: dict, pattern: str, line: str = "ops") -> int:
    lo, hi = trace["window"]
    rx = re.compile(pattern)
    return sum(1 for dev in trace["devices"].values() for e in dev[line]
               if rx.search(e[0]) and lo <= e[1] < hi)


# ---------------------------------------------------------------------------
# the breakdown the result line carries
# ---------------------------------------------------------------------------
def stable_name(name: str) -> str:
    """An operation's name without the numeric suffixes XLA appends.  A
    TPU trace names an operation by its HLO text (``%fusion.12 = ...``):
    the instruction's name is kept, and a Pallas kernel is named by its
    call target."""
    if 'custom_call_target="tpu_custom_call"' in name:
        return "tpu_custom_call"
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def self_times(events, lo: float, hi: float) -> list:
    """``[[name, ns], ...]``: each event's time inside ``[lo, hi]`` less the
    time of the events nested in it (one line's events nest as a stack)."""
    out, stack = [], []
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        a, b = max(s, lo), min(s + d, hi)
        while stack and stack[-1][1] <= s:
            stack.pop()
        own = max(0.0, b - a)
        if stack and own:
            out[stack[-1][2]][1] -= own
        out.append([name, own])
        stack.append((s, s + d, len(out) - 1))
    return out


def top_ops(trace: dict, n: int = 10) -> list:
    """``[[name, seconds], ...]``: the device operations that took the most
    self time in the window, per device on average."""
    lo, hi = trace["window"]
    tot = defaultdict(float)
    for dev in trace["devices"].values():
        for name, t in self_times(dev["ops"], lo, hi):
            if t > 0:
                tot[stable_name(name)] += t
    ndev = len(trace["devices"])
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / ndev / 1e9] for k, v in ranked]


def idle_gaps(trace: dict, n: int = 10) -> list:
    """``[[activity, seconds], ...]``: the device's idle time in the window
    (first device), by the innermost benchmark host span under each gap
    ("host.other" where none is), largest first."""
    lo, hi = trace["window"]
    dev = next(iter(trace["devices"].values()))
    busy = union(clip(dev["ops"], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append([t, a])
        t = max(t, b)
    if hi > t:
        gaps.append([t, hi])
    segs = _span_segments([h for h in trace["host"] if h[0] != WINDOW])
    tot = defaultdict(float)
    j = 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        covered, k = 0.0, j
        while k < len(segs) and segs[k][0] < b:
            u, v = max(a, segs[k][0]), min(b, segs[k][1])
            if v > u:
                tot[segs[k][2]] += v - u
                covered += v - u
            k += 1
        tot["host.other"] += (b - a) - covered
    ranked = sorted(((k, v) for k, v in tot.items() if v > 0),
                    key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def _span_segments(spans) -> list:
    """``[[start, end, name], ...]``: the timeline cut where any span
    starts or ends, each piece named by the shortest span over it."""
    marks = sorted({t for _, s, d in spans for t in (s, s + d)})
    by_start = sorted(spans, key=lambda h: h[1])
    out, active, i = [], [], 0
    for lo, hi in zip(marks, marks[1:]):
        while i < len(by_start) and by_start[i][1] <= lo:
            active.append(by_start[i])
            i += 1
        active = [h for h in active if h[1] + h[2] > lo]
        if active:
            out.append([lo, hi, min(active, key=lambda h: h[2])[0]])
    return out
