"""Posterior-predictive serving from the chain bank: queries/sec and latency
percentiles vs. chain count and shard count.

A :class:`~repro.cluster.serve.ServeEngine` answers a mixed stream of
batched predictive requests (request sizes drawn from a ladder, so the
shape buckets are genuinely exercised) against a PolyRegression posterior
bank drawn in closed form — this benchmarks the *serving* path, not
training.  Each row reports end-to-end queries/sec, request latency
percentiles, and the trace count (must stay at one per shape bucket or the
run fails).  The shard sweep runs on whatever devices exist; CI forces 8
host devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``python benchmarks/bench_serve.py [--smoke] [--out BENCH_serve.json]``
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import instrument
from repro.cluster import ServeEngine, bucket_size
from repro.core import PolyRegression
from repro.launch.mesh import make_data_mesh
from repro.models import regression_predict
from repro.obs import registry

SIGMA = 1e-3


def _bank(reg: PolyRegression, chains: int, seed: int) -> jnp.ndarray:
    """Chain-stacked params drawn from the closed-form Gibbs posterior
    N(mu, sigma * Sigma) — a converged bank without paying for training."""
    mu, cov, _ = reg.posterior_moments(sigma=SIGMA)
    chol = np.linalg.cholesky(np.asarray(cov, np.float64))
    eps = np.random.default_rng(seed).standard_normal((chains, reg.d))
    return jnp.asarray(np.asarray(mu) + eps @ chol.T, jnp.float32)


def _measure(engine: ServeEngine, *, requests: int, max_queries: int,
             seed: int) -> dict:
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, max_queries + 1, size=requests)
    # host-resident requests, as a serving front end would hand them over
    stream = [rng.uniform(-1.0, 1.0, int(n)).astype(np.float32)
              for n in sizes]
    buckets = sorted({bucket_size(int(n)) for n in sizes})
    for n in buckets:  # compile every bucket off the clock
        jax.block_until_ready(engine(np.zeros(n, np.float32)).mean)
        engine(np.ones(max(n - 1, 1), np.float32))  # warm the pad scratch too

    lat = []
    t_all = time.time()
    # any trace or pad alloc inside this block is a stream-path regression;
    # the report's stream_flags() feed the row fields check_bench gates on
    with instrument() as rep:
        for q in stream:
            t0 = time.time()
            jax.block_until_ready(engine(q).mean)
            lat.append(time.time() - t0)
    total_s = time.time() - t_all
    lat_ms = np.asarray(lat) * 1e3
    p50, p90, p99 = (float(np.percentile(lat_ms, p)) for p in (50, 90, 99))
    return {
        "chains": engine.num_chains,
        "shards": (engine.mesh.shape[engine.chain_axis]
                   if engine.mesh is not None else 1),
        "requests": requests,
        "queries": int(sizes.sum()),
        "buckets": len(buckets),
        "traces": engine.num_traces,
        # host padding must reuse the per-rung scratch: zero allocations
        # (device or host) per request once the rungs are warm
        **rep.stream_flags(),
        "qps": round(float(sizes.sum()) / total_s, 1),
        "requests_per_s": round(requests / total_s, 1),
        "p50_ms": round(p50, 3),
        "p90_ms": round(p90, 3),
        "p99_ms": round(p99, 3),
    }


def run(chain_sweep=(8, 64, 256), shard_sweep=(2, 4, 8), requests: int = 200,
        max_queries: int = 64, seed: int = 0) -> dict:
    reg = PolyRegression.make(jax.random.PRNGKey(seed))
    predict = regression_predict(reg)
    rows = []
    for chains in chain_sweep:
        eng = ServeEngine(predict_fn=predict, params=_bank(reg, chains, seed))
        rows.append(_measure(eng, requests=requests, max_queries=max_queries,
                             seed=seed + 1))
    chains = max(chain_sweep)
    n_dev = len(jax.devices())
    for shards in shard_sweep:
        if shards > n_dev or chains % shards:
            continue
        mesh = make_data_mesh(shards)
        eng = ServeEngine(predict_fn=predict,
                          params=_bank(reg, chains, seed), mesh=mesh)
        rows.append(_measure(eng, requests=requests, max_queries=max_queries,
                             seed=seed + 1))
    return {
        "config": {"chain_sweep": list(chain_sweep), "requests": requests,
                   "max_queries": max_queries, "seed": seed,
                   "devices": n_dev, "sigma": SIGMA},
        "rows": rows,
    }


def _row(result: dict) -> dict:
    """CSV row for benchmarks.run: the largest unsharded configuration."""
    best = [r for r in result["rows"] if r["shards"] == 1][-1]
    return {
        "bench": "serve", "us_per_call": round(1e6 / best["qps"], 1),
        "chains": best["chains"], "qps": best["qps"],
        "p50_ms": best["p50_ms"], "p99_ms": best["p99_ms"],
        "traces": best["traces"],
    }


SMOKE_KW = dict(chain_sweep=(8, 32), shard_sweep=(2, 4, 8), requests=60,
                max_queries=32)


def main(fast: bool = True):
    return [_row(run(**(SMOKE_KW if fast else {})))]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (8/32 chains, 60 requests)")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()
    result = run(**(SMOKE_KW if args.smoke else {}))
    stem = args.out[:-5] if args.out.endswith(".json") else args.out
    registry().write_snapshot(f"{stem}.metrics.json")
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(_row(result)))
    for r in result["rows"]:
        print(f"  chains={r['chains']:4d} shards={r['shards']} "
              f"qps={r['qps']:10.1f} p50={r['p50_ms']:.2f}ms "
              f"p99={r['p99_ms']:.2f}ms traces={r['traces']}")
    print(f"wrote {args.out} (+ .metrics.json)")
    if any(r["retraced_in_stream"] for r in result["rows"]):
        raise SystemExit("serve path retraced inside a request stream "
                         "(more than one trace per shape bucket)")
    if any(r["pad_allocs_in_stream"] for r in result["rows"]):
        raise SystemExit("request padding allocated per request instead of "
                         "reusing the per-rung scratch")
