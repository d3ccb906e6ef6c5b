"""Kernel microbenchmarks: fused langevin_update / delay_gather.

On this CPU container the Pallas kernels run in interpret mode (Python), so
wall-clock numbers are reported for the pure-jnp REFERENCE path (what a
TPU-less user gets), plus the HBM-traffic model for the kernel vs the
unfused XLA graph — the quantity the fusion actually improves on TPU:

  unfused: RNG writes noise (W), update reads x, g, noise + writes x' = 5N
  fused:   reads x, g + writes x' = 3N    (-40% traffic)
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels.ref import langevin_update_ref, delay_gather_ref
from repro.samplers.transforms import noise_like, sgld_apply


def _time(fn, *args, iters=20):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6  # us


def run(n: int = 1 << 20):
    rows = []
    x = jax.random.normal(jax.random.PRNGKey(0), (n,))
    g = jax.random.normal(jax.random.PRNGKey(1), (n,))
    seed = jnp.array([1, 2], jnp.uint32)

    # unfused XLA path (jax.random.normal + update)
    @jax.jit
    def unfused(x, g, key):
        noise = noise_like(key, {"p": x}, jnp.float32(0.05), jnp.float32)
        return sgld_apply({"p": x}, {"p": g}, jnp.float32(0.01), noise)["p"]

    us_unfused = _time(unfused, x, g, jax.random.PRNGKey(2))

    # fused-math reference (same threefry math the Pallas kernel runs)
    rows2d = n // 1024
    x2 = x.reshape(rows2d, 1024)
    g2 = g.reshape(rows2d, 1024)
    fused_ref = jax.jit(lambda x, g: langevin_update_ref(x, g, seed, 0.01, 0.05))
    us_fused = _time(fused_ref, x2, g2)

    itemsize = 4
    rows.append({"bench": "kernel_langevin", "n": n,
                 "us_unfused_xla": round(us_unfused, 1),
                 "us_fused_ref": round(us_fused, 1),
                 "traffic_unfused_bytes": 5 * n * itemsize,
                 "traffic_fused_bytes": 3 * n * itemsize,
                 "traffic_saving": "40%"})

    depth = 5
    h = jax.random.normal(jax.random.PRNGKey(3), (depth, n))
    slots = jax.random.randint(jax.random.PRNGKey(4), (n,), 0, depth)
    us_gather = _time(jax.jit(delay_gather_ref), h, slots)
    rows.append({"bench": "kernel_delay_gather", "n": n, "depth": depth,
                 "us_ref": round(us_gather, 1),
                 "traffic_kernel_bytes": (depth + 2) * n * itemsize})
    return rows


def main(fast=True):
    return run(n=(1 << 18) if fast else (1 << 22))


if __name__ == "__main__":
    for r in run():
        print(r)
