"""Data-series generation & loading (paper Sec. 6 "Datasets").

The paper's synthetic workload is a Gaussian random walk ("shown to
effectively simulate real-world financial data"); real workloads are sliding
windows over long recordings (seismic/astronomy), z-normalized.  We provide
both: the random-walk generator, and a sliding-window extractor usable over
any long 1-D signal (plus a synthetic 'seismic-like' signal so the real-data
code path is exercised without the 100GB download).
"""
from __future__ import annotations

import functools
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.summarization import znormalize

__all__ = ["random_walk", "random_walk_blocks", "sliding_windows",
           "synthetic_signal", "series_batches", "query_workload"]


def random_walk(key: jax.Array, n: int, length: int = 256,
                znorm: bool = True) -> jax.Array:
    """Paper's generator: steps ~ N(0,1), cumulatively summed."""
    steps = jax.random.normal(key, (n, length))
    x = jnp.cumsum(steps, axis=-1)
    return znormalize(x) if znorm else x


@functools.partial(jax.jit, static_argnames=("n", "length", "block"))
def random_walk_blocks(key: jax.Array, n: int, length: int = 256,
                       block: int = 1 << 16) -> jax.Array:
    """``n`` z-normalized random walks made on the device ``block`` rows
    at a time in one program, so a collection that fills most of a
    chip's memory needs little more than itself while it is made.
    Block ``i`` is ``random_walk(fold_in(key, i), block, length)``."""
    nb = -(-n // block)
    out = jax.lax.map(
        lambda i: random_walk(jax.random.fold_in(key, i), block, length),
        jnp.arange(nb))
    return out.reshape(nb * block, length)[:n]


def synthetic_signal(key: jax.Array, total_len: int,
                     n_modes: int = 24) -> jax.Array:
    """Seismic-like long signal: superposed decaying oscillations + noise."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    t = jnp.arange(total_len, dtype=jnp.float32)
    freqs = jax.random.uniform(k1, (n_modes,), minval=1e-4, maxval=5e-2)
    phases = jax.random.uniform(k2, (n_modes,), maxval=2 * jnp.pi)
    amps = jax.random.exponential(k3, (n_modes,))
    sig = jnp.sum(amps[:, None] * jnp.sin(freqs[:, None] * t[None, :]
                                          + phases[:, None]), axis=0)
    return sig + 0.3 * jax.random.normal(k4, (total_len,))


def sliding_windows(signal: jax.Array, length: int = 256, step: int = 4,
                    znorm: bool = True) -> jax.Array:
    """Extract overlapping subsequences (paper: step 4 for seismic, 1 astro)."""
    n = (signal.shape[0] - length) // step + 1
    starts = jnp.arange(n) * step
    idx = starts[:, None] + jnp.arange(length)[None, :]
    x = signal[idx]
    return znormalize(x) if znorm else x


def series_batches(key: jax.Array, total: int, batch: int,
                   length: int = 256) -> Iterator[np.ndarray]:
    """Streaming batches for LSM ingestion experiments."""
    done = 0
    while done < total:
        key, sub = jax.random.split(key)
        n = min(batch, total - done)
        yield np.asarray(random_walk(sub, n, length))
        done += n


def query_workload(key: jax.Array, dataset: jax.Array, n_queries: int,
                   noise: float = 0.1,
                   from_dataset_frac: float = 0.5) -> jax.Array:
    """Paper-style query workload: randomly selected series (optionally
    perturbed) — 'locate whether this series or a similar one exists'."""
    k1, k2, k3 = jax.random.split(key, 3)
    n = dataset.shape[0]
    idx = jax.random.randint(k1, (n_queries,), 0, n)
    base = dataset[idx]
    fresh = random_walk(k2, n_queries, dataset.shape[1])
    take_base = (jax.random.uniform(k3, (n_queries, 1))
                 < from_dataset_frac)
    q = jnp.where(take_base, base, fresh)
    if noise > 0:
        k4 = jax.random.fold_in(k3, 1)
        q = q + noise * jax.random.normal(k4, q.shape)
    return znormalize(q)
