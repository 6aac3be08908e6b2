"""PAA / SAX / invSAX summarization of data series (paper Secs. 2, 4.1).

A data series is a z-normalized float vector of length ``L``.  Its PAA
(Piecewise Aggregate Approximation) is the mean over ``w`` equal segments; the
SAX word quantizes each PAA value into ``2**b`` regions whose boundaries are
standard-normal quantiles ("breakpoints"), so regions are equiprobable for
z-normalized data.  The *sortable* summarization (invSAX) bit-interleaves the
SAX word onto a z-order curve (see :mod:`repro.core.keys`).

The lower-bounding distance ``mindist`` (used by SIMS exact search to prune)
is the classic iSAX bound: per segment, the squared distance from the query's
PAA value to the candidate's region, scaled by L/w — provably <= true ED.
Sortable summarizations keep *identical* pruning power (Sec. 4.1): mindist
only reads the SAX codes, which the z-order key preserves bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from scipy.special import ndtri

from . import keys as K

__all__ = [
    "SummaryConfig",
    "breakpoints",
    "region_bounds",
    "znormalize",
    "paa",
    "sax_encode",
    "summarize",
    "invsax_keys",
    "mindist_sq",
    "mindist_sq_batch",
    "pairwise_sum",
    "sum_sq",
    "sq_dist",
    "euclidean_sq",
    "euclidean_sq_batch",
]


@dataclasses.dataclass(frozen=True)
class SummaryConfig:
    """Summarization hyper-parameters (paper default: 16 segments, 8 bits)."""
    series_len: int = 256     # L
    segments: int = 16        # w
    bits: int = 8             # b (cardinality 2**b per segment)

    def __post_init__(self):
        if self.series_len % self.segments != 0:
            raise ValueError(
                f"series_len={self.series_len} must be divisible by "
                f"segments={self.segments}")
        if not (1 <= self.bits <= 8):
            raise ValueError("bits must be in [1, 8]")

    @property
    def n_words(self) -> int:
        return K.n_key_words(self.segments, self.bits)

    @property
    def cardinality(self) -> int:
        return 1 << self.bits

    @property
    def seg_len(self) -> int:
        return self.series_len // self.segments


@functools.lru_cache(maxsize=None)
def _breakpoints_np(bits: int) -> np.ndarray:
    """Standard-normal quantile breakpoints: 2**b - 1 boundaries (float32).

    Computed with the inverse normal CDF (ndtri); cached host-side so every
    op/kernel shares bit-identical tables.
    """
    card = 1 << bits
    qs = np.arange(1, card, dtype=np.float64) / card
    return ndtri(qs).astype(np.float32)


def breakpoints(bits: int) -> jax.Array:
    """Region boundaries, shape ``[2**b - 1]``, ascending."""
    return jnp.asarray(_breakpoints_np(bits))


def region_bounds(bits: int) -> Tuple[jax.Array, jax.Array]:
    """Per-code (lower, upper) bounds, shape ``[2**b]`` each, +/-inf at ends."""
    bps = _breakpoints_np(bits)
    lower = np.concatenate([[-np.inf], bps]).astype(np.float32)
    upper = np.concatenate([bps, [np.inf]]).astype(np.float32)
    return jnp.asarray(lower), jnp.asarray(upper)


def znormalize(x: jax.Array, eps: float = 1e-8) -> jax.Array:
    """Z-normalize each series (paper Sec. 2: required preprocessing)."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    sd = jnp.std(x, axis=-1, keepdims=True)
    return (x - mu) / (sd + eps)


def paa(x: jax.Array, segments: int) -> jax.Array:
    """Piecewise Aggregate Approximation: ``[..., L] -> [..., w]``."""
    *lead, L = x.shape
    if L % segments != 0:
        raise ValueError(f"series length {L} not divisible by w={segments}")
    return jnp.mean(x.reshape(*lead, segments, L // segments), axis=-1)


def sax_encode(paa_vals: jax.Array, bits: int) -> jax.Array:
    """Quantize PAA values into SAX codes ``[..., w]`` (uint8 region ids)."""
    bps = breakpoints(bits)
    # number of breakpoints <= value  ==  region index in [0, 2**b - 1]
    codes = jnp.searchsorted(bps, paa_vals, side="right")
    return codes.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("cfg",))
def summarize(x: jax.Array, cfg: SummaryConfig) -> Tuple[jax.Array, jax.Array]:
    """Series ``[N, L]`` -> (PAA ``[N, w]`` float32, SAX codes ``[N, w]`` uint8)."""
    p = paa(x.astype(jnp.float32), cfg.segments)
    return p, sax_encode(p, cfg.bits)


@functools.partial(jax.jit, static_argnames=("cfg",))
def invsax_keys(codes: jax.Array, cfg: SummaryConfig) -> jax.Array:
    """SAX codes -> sortable z-order keys ``[N, n_words]`` uint32."""
    return K.interleave_codes(codes, w=cfg.segments, b=cfg.bits)


@functools.partial(jax.jit, static_argnames=("cfg",))
def mindist_sq(query_paa: jax.Array, codes: jax.Array,
               cfg: SummaryConfig) -> jax.Array:
    """Squared iSAX lower bound between a query PAA ``[w]`` and codes ``[N, w]``.

    mindist(q, c)^2 = (L/w) * sum_j  dist(q_j, region(c_j))^2  <=  ED(q, s)^2
    for every series ``s`` whose SAX word is ``c``.
    """
    lower, upper = region_bounds(cfg.bits)
    lb = lower[codes.astype(jnp.int32)]          # [N, w]
    ub = upper[codes.astype(jnp.int32)]
    q = query_paa[None, :]
    below = jnp.where(q < lb, lb - q, 0.0)
    above = jnp.where(q > ub, q - ub, 0.0)
    d = below + above
    return (cfg.series_len / cfg.segments) * jnp.sum(d * d, axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg",))
def mindist_sq_batch(query_paas: jax.Array, codes: jax.Array,
                     cfg: SummaryConfig) -> jax.Array:
    """Batched iSAX lower bound: queries ``[Q, w]``, codes ``[N, w]`` -> ``[Q, N]``.

    Semantically ``vmap(mindist_sq)`` — one pass over the codes serves the
    whole query batch (the batched SIMS scan of ``exact_search_batch``).
    """
    lower, upper = region_bounds(cfg.bits)
    lb = lower[codes.astype(jnp.int32)]          # [N, w]
    ub = upper[codes.astype(jnp.int32)]
    q = query_paas[:, None, :]                   # [Q, 1, w]
    below = jnp.where(q < lb[None], lb[None] - q, 0.0)
    above = jnp.where(q > ub[None], q - ub[None], 0.0)
    d = below + above
    return (cfg.series_len / cfg.segments) * jnp.sum(d * d, axis=-1)


def pairwise_sum(s: jax.Array) -> jax.Array:
    """Sum over the last axis, added in one fixed order.

    A pairwise tree of elementwise adds: element ``i`` meets element
    ``i + h`` as ``h`` halves from the width (zero-padded to a power of
    two) down to 1.  Every output word is then the same sequence of IEEE
    adds whatever the other dimensions are.  ``jnp.sum`` leaves the order
    to the compiler, which may choose it per shape, so a row's distance
    could change with the batch size or the block it was verified in.
    Every distance an answer carries is added in this order, including
    inside the Pallas kernels.
    """
    n = s.shape[-1]
    p = 1 << (n - 1).bit_length()
    if p != n:
        s = jnp.pad(s, [(0, 0)] * (s.ndim - 1) + [(0, p - n)])
    while s.shape[-1] > 1:
        h = s.shape[-1] // 2
        s = s[..., :h] + s[..., h:]
    return s[..., 0]


def sum_sq(diff: jax.Array) -> jax.Array:
    """Sum of squares over the last axis: each square rounded to f32 on
    its own, then :func:`pairwise_sum`.  The ``maximum`` with zero leaves
    every square (and a NaN) as it is, but stands between the multiply
    and the first add, so a compiler cannot contract the two into a fused
    multiply-add (which rounds once, and is formed or not depending on
    the fusion)."""
    return pairwise_sum(jnp.maximum(diff * diff, 0.0))


@jax.jit
def sq_dist(series: jax.Array, queries: jax.Array) -> jax.Array:
    """Squared ED between broadcast-compatible ``[..., L]`` arrays."""
    return sum_sq(series - queries)


def euclidean_sq(query: jax.Array, series: jax.Array) -> jax.Array:
    """Squared ED between query ``[L]`` and series ``[N, L]`` -> ``[N]``."""
    return sq_dist(series, query[None, :])


def euclidean_sq_batch(queries: jax.Array, series: jax.Array) -> jax.Array:
    """Squared ED between queries ``[Q, L]`` and series ``[N, L]`` -> ``[Q, N]``."""
    return sq_dist(series[None, :, :], queries[:, None, :])


@functools.partial(jax.jit, static_argnames=("cfg",))
def mindist_sq_table(query_paa: jax.Array, codes: jax.Array,
                     cfg: SummaryConfig) -> jax.Array:
    """Table-driven mindist: fold the query into a [w, 2**b] per-segment
    distance table, then one flat gather per code (§Perf Coconut iteration:
    replaces two bound gathers + compare/select arithmetic per element with
    a single take — the scan becomes purely bandwidth-bound).

    Numerically identical to :func:`mindist_sq`.
    """
    lower, upper = region_bounds(cfg.bits)
    q = query_paa[:, None]                       # [w, 1]
    below = jnp.where(q < lower[None, :], lower[None, :] - q, 0.0)
    above = jnp.where(q > upper[None, :], q - upper[None, :], 0.0)
    d = below + above
    table = (d * d)                              # [w, 2**b]
    card = 1 << cfg.bits
    flat = table.reshape(-1)                     # [w * 2**b]
    idx = codes.astype(jnp.int32) + (
        jnp.arange(cfg.segments, dtype=jnp.int32) * card)[None, :]
    per_seg = jnp.take(flat, idx)                # [N, w], one gather
    return (cfg.series_len / cfg.segments) * jnp.sum(per_seg, axis=-1)
