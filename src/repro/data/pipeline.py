"""Host-side data pipeline: double-buffered prefetch + device placement.

The training loop wants batches produced off the critical path:
``Prefetcher`` generates the next batch on a background thread while the
current step runs, and (when a mesh is given) places it with the batch
sharding the step expects.  :func:`ar1_stream`
generates the dependent (non-i.i.d.) minibatch sequence used by the
Chau-et-al.-shaped benchmark scenario.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

PyTree = Any


def ar1_stream(key: jax.Array, *, steps: int, batch: int, d: int,
               rho: float = 0.9, mean: float = 0.0, scale: float = 1.0,
               dtype=jnp.float32) -> jax.Array:
    """Generate a dependent AR(1) minibatch sequence (Chau et al.-shaped).

    SGLD convergence results usually assume i.i.d. minibatches; Chau,
    Moulines & Rásonyi analyse SGLD when the data arrive as a *dependent*
    stream instead.  This produces the simplest such stream: each of the
    ``batch * d`` example coordinates follows an independent stationary
    AR(1) chain across steps,

        e_{t+1} = mean + rho * (e_t - mean) + scale * sqrt(1 - rho^2) * xi_t,

    with ``e_0`` drawn from the stationary marginal ``N(mean, scale^2)``.
    The innovation scaling keeps the *marginal* of every step equal to that
    of an i.i.d. ``N(mean, scale^2)`` stream, so swapping this in for an
    i.i.d. stream changes only the temporal dependence — the stationary
    target of a data-noise-driven scenario is unchanged.

    Args:
        key: PRNG key; the stream is a pure function of it (bit-for-bit
            reproducible from the seed — pinned by ``tests/test_zoo.py``).
        steps: number of minibatches in the sequence (the scan length).
        batch: examples per minibatch.
        d: feature dimension of each example.
        rho: AR(1) autocorrelation in ``[0, 1)``; ``rho=0`` recovers an
            i.i.d. stream.
        mean / scale: stationary marginal moments.
        dtype: element dtype of the returned stream.

    Returns:
        ``(steps, batch, d)`` array of minibatches, ready to feed to
        ``Sampler.run`` / ``Engine`` as the per-step batch axis.
    """
    if not 0.0 <= rho < 1.0:
        raise ValueError(f"rho must be in [0, 1), got {rho}")
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    k0, k_noise = jax.random.split(key)
    e0 = mean + scale * jax.random.normal(k0, (batch, d), dtype)
    innovations = jax.random.normal(k_noise, (steps - 1, batch, d), dtype)
    innov_scale = jnp.asarray(scale * (1.0 - rho ** 2) ** 0.5, dtype)

    def step(prev, xi):
        nxt = mean + rho * (prev - mean) + innov_scale * xi
        return nxt, nxt

    _, tail = jax.lax.scan(step, e0, innovations)
    return jnp.concatenate([e0[None], tail], axis=0)


class Prefetcher:
    """Wrap a batch-generating callable into a prefetching iterator.

    batch_fn(key) -> pytree;  keys are split from ``key`` per step.
    """

    def __init__(self, batch_fn: Callable[[jax.Array], PyTree], key: jax.Array,
                 mesh=None, batch_axes=("data",), depth: int = 2):
        self.batch_fn = batch_fn
        self.key = key
        self.mesh = mesh
        self.batch_axes = tuple(batch_axes)
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self._worker, daemon=True)
        self.thread.start()

    def _place(self, batch: PyTree) -> PyTree:
        if self.mesh is None:
            return batch

        def put(x):
            spec = P(self.batch_axes if self.batch_axes else None,
                     *([None] * (x.ndim - 1))) if x.ndim else P()
            return jax.device_put(x, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map(put, batch)

    def _worker(self):
        key = self.key
        while not self.stop.is_set():
            key, sub = jax.random.split(key)
            batch = self._place(self.batch_fn(sub))
            while not self.stop.is_set():
                try:
                    self.q.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def __iter__(self) -> Iterator[PyTree]:
        return self

    def __next__(self) -> PyTree:
        return self.q.get()

    def close(self):
        self.stop.set()
        try:
            while True:
                self.q.get_nowait()
        except queue.Empty:
            pass
        self.thread.join(timeout=2.0)
