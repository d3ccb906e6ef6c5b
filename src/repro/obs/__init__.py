"""repro.obs — observability for the async sampler, serve, and decode paths.

The paper's claim is about *wall-clock* behavior under asynchrony, so time
has to be a first-class, exportable quantity — not a benchmark total.  Three
layers, all host-side by construction (safe on compiled paths):

- :mod:`repro.obs.trace` — a low-overhead span tracer (``span("decode.
  generate", **attrs)``, parent-linked per-thread trees, disabled-by-default
  null path); enabled live spans also land in the ``jax.profiler`` trace;
- :mod:`repro.obs.metrics` — a process-global registry of counters, gauges,
  and fixed-bucket histograms (per-token latency, per-commit staleness, W2,
  grad evals, bank utilization) with JSON snapshot and Prometheus text
  exposition;
- :mod:`repro.obs.timeline` — Chrome-trace-event export of cluster commit
  schedules and decode request streams, openable directly in Perfetto /
  ``chrome://tracing`` (``scripts/obstool.py`` summarizes them).

The runtime invariants bus (:mod:`repro.analysis.instrument`) feeds this
layer: XLA compile wall-time lands in the registry, and the benchmarks
write one metrics snapshot + timeline next to each ``BENCH_*.json``.
"""

from repro.obs import metrics, timeline, trace  # noqa: F401
from repro.obs.metrics import Registry, registry  # noqa: F401
from repro.obs.timeline import (  # noqa: F401
    cluster_timeline,
    decode_timeline,
    paged_timeline,
    to_chrome_trace,
    write_chrome_trace,
)
from repro.obs.trace import Span, Tracer, span, tracer  # noqa: F401
