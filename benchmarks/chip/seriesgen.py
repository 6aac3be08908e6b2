"""Data generators of the benchmark: the collections, streams and query
pools of every cell, made on the device from a PRNG key.

Copied from ``src/repro/data/series.py`` and ``znormalize`` of
``src/repro/core/summarization.py`` (same arithmetic), so that no change
to the program can change what the benchmark feeds it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def znormalize(x: jax.Array, eps: float = 1e-8) -> jax.Array:
    """Each series to mean 0 and standard deviation 1."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    sd = jnp.std(x, axis=-1, keepdims=True)
    return (x - mu) / (sd + eps)


def random_walk(key: jax.Array, n: int, length: int) -> jax.Array:
    """Coconut's synthetic series: N(0, 1) steps, summed, z-normalized."""
    return znormalize(jnp.cumsum(jax.random.normal(key, (n, length)),
                                 axis=-1))


@functools.partial(jax.jit, static_argnames=("n", "length", "block"))
def random_walk_blocks(key: jax.Array, n: int, length: int,
                       block: int) -> jax.Array:
    """``n`` random walks made ``block`` rows at a time in one program,
    so a collection that fills most of a chip needs little more than
    itself while it is made.  Block ``i`` is
    ``random_walk(fold_in(key, i), block, length)``."""
    nb = -(-n // block)
    out = jax.lax.map(
        lambda i: random_walk(jax.random.fold_in(key, i), block, length),
        jnp.arange(nb))
    return out.reshape(nb * block, length)[:n]


def synthetic_signal(key: jax.Array, total_len: int,
                     n_modes: int = 24) -> jax.Array:
    """A long seismic-like signal: decaying oscillations plus noise."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    t = jnp.arange(total_len, dtype=jnp.float32)
    freqs = jax.random.uniform(k1, (n_modes,), minval=1e-4, maxval=5e-2)
    phases = jax.random.uniform(k2, (n_modes,), maxval=2 * jnp.pi)
    amps = jax.random.exponential(k3, (n_modes,))
    sig = jnp.sum(amps[:, None] * jnp.sin(freqs[:, None] * t[None, :]
                                          + phases[:, None]), axis=0)
    return sig + 0.3 * jax.random.normal(k4, (total_len,))


@functools.partial(jax.jit, static_argnames=("n", "length", "step"))
def sliding_windows(key: jax.Array, n: int, length: int,
                    step: int) -> jax.Array:
    """``n`` z-normalized windows, ``step`` points apart, of one
    :func:`synthetic_signal` just long enough to hold them."""
    sig = synthetic_signal(key, (n - 1) * step + length)
    idx = (jnp.arange(n) * step)[:, None] + jnp.arange(length)[None, :]
    return znormalize(sig[idx])


@functools.partial(jax.jit, static_argnames=("n_queries",))
def noisy_members(key: jax.Array, dataset: jax.Array, n_queries: int,
                  noise: float) -> jax.Array:
    """Coconut's query workload with every query taken from the data:
    a randomly chosen member plus ``noise`` N(0, 1) per point,
    z-normalized ("does this series or a similar one exist")."""
    k1, _, k3 = jax.random.split(key, 3)
    idx = jax.random.randint(k1, (n_queries,), 0, dataset.shape[0])
    q = dataset[idx] + noise * jax.random.normal(
        jax.random.fold_in(k3, 1), (n_queries, dataset.shape[1]))
    return znormalize(q)
