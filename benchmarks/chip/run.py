"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload qwen3-4b.sample --seed 7 \\
        --seconds 40 --trace 0

The cell, its configuration and its traffic mix come from ``BENCHMARK.json``
at the checkout root.  With ``--trace 0`` the last line of standard output
carries the cell's end-to-end metrics; with ``--trace 1`` the window is
recorded by the profiler and the line carries the per-layer metrics, each
read by its own file under ``metrics/``.  Every line names the device.
Where JAX finds no TPU, or fewer chips than the cell asks for, the run
exits nonzero before any work and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import harness  # noqa: E402


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _jax_env() -> None:
    """The compile cache at its fixed path in the checkout, every program
    cached however quick its compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))


def result_line(man: dict, workload: str, out, dev0, count: int,
                trace: bool, per_layer: dict | None) -> dict:
    units = {m["name"]: m["unit"]
             for m in man["end_to_end"] + man["per_layer"]}
    vals = per_layer if trace else {
        m["name"]: out.e2e[m["name"]]
        for m in harness.cell_metrics(man, workload, "end_to_end")}
    line = {
        "correct": out.correct,
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in vals.items()
                    if v is not None and math.isfinite(v)},
        "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                   "count": count,
                   "memory_peak_bytes": out.memory_peak_bytes},
    }
    tr = out.layer.get("trace")
    if trace and tr is not None:
        from chipbench import traces

        line["device"]["busy_s"] = traces.busy_s_mean(tr)
        line["device"]["window_s"] = traces.window_s(tr)
        line["breakdown"] = {"device_ops": traces.top_ops(tr),
                             "idle_gaps": traces.idle_gaps(tr)}
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in out.compared.items()}
    return line


def main(argv=None) -> int:
    args = _args(argv)
    man = harness.manifest()
    cell = harness.cell(man, args.workload)
    _jax_env()
    import jax

    devices = jax.devices()
    dev0 = devices[0]
    print(f"platform={dev0.platform} device_kind={dev0.device_kind} "
          f"count={len(devices)}")
    if dev0.platform != "tpu":
        print("no TPU found: the benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peak = harness.peaks(dev0.device_kind)
    conf = harness.config_file(man, cell["config"])
    traffic = harness.traffic_file(cell["traffic"])
    ctx = SimpleNamespace(conf=conf, cfg=harness.arch_config(conf),
                          traffic=traffic, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          t_start=T_START,
                          devices=devices[:cell["chips"]])
    if args.trace:
        from repro.obs import trace as obs_trace

        obs_trace.enable()
    out = harness.driver(traffic).run(ctx)
    per_layer = None
    if args.trace:
        layer = dict(out.layer, conf=conf, traffic=traffic, peak=peak,
                     chips=out.chips)
        per_layer = {m["name"]: harness.reader(m["name"]).read(layer)
                     for m in harness.cell_metrics(man, args.workload,
                                                   "per_layer")}
    for note in out.notes:
        print(note)
    line = result_line(man, args.workload, out, dev0, len(devices),
                       bool(args.trace), per_layer)
    for name, (v, lim) in out.compared.items():
        print(f"compared {name}: {v!r} limit {lim!r}", file=sys.stderr)
    print(f"correct={out.correct}", file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
