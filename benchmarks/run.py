"""Benchmark runner: one module per paper table/figure family.

``python -m benchmarks.run``           fast pass, prints CSV
``python -m benchmarks.run --full``    full paper grids (slow, writes JSONs)
``python -m benchmarks.run --only regression,rica``
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (
    bench_cluster,
    bench_decode,
    bench_engine,
    bench_kernels,
    bench_regression,
    bench_rica,
    bench_serve,
    bench_speedup,
    bench_tau_sweep,
)

BENCHES = {
    "regression": bench_regression.main,   # paper Figs 1-4, 9-15
    "rica": bench_rica.main,               # paper Figs 5-8, 11-12, 16-17
    "speedup": bench_speedup.main,         # paper sub-figures (b)
    "tau_sweep": bench_tau_sweep.main,     # Corollary 2.1
    "kernels": bench_kernels.main,         # Pallas hot-path
    "engine": bench_engine.main,           # scan-chunked Engine vs host loop
    "cluster": bench_cluster.main,         # C-chain ensemble W2 + speedup
    "serve": bench_serve.main,             # chain-bank predictive serving
    "decode": bench_decode.main,           # streaming BMA decode tokens/sec
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    args, _ = ap.parse_known_args()
    names = args.only.split(",") if args.only else list(BENCHES)

    print("name,us_per_call,derived")
    failures = []
    for name in names:
        t0 = time.time()
        try:
            rows = BENCHES[name](fast=not args.full)
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            print(f"{name},ERROR,{e!r}")
            continue
        wall_us = (time.time() - t0) * 1e6
        for row in rows:
            us = row.pop("us_per_call", round(wall_us / max(len(rows), 1), 1))
            tag = row.pop("bench", name)
            derived = ";".join(f"{k}={v}" for k, v in row.items())
            print(f"{tag},{us},{derived}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
