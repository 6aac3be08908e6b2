"""The plain reference of every cell: exact k nearest neighbours by
scanning every row, and the comparisons that decide ``correct``.

Distances are squared Euclidean distances in float32, each square
rounded on its own and the squares added pairwise in one fixed order
(element i with element i + h, h = L/2, ..., 1).  That is the order the
index documents for its answers, so an exact answer must equal the
reference's ids and, to the last bit or two, its distances.

Copied from ``chip_smoke.py`` (``pairwise_sq_dist``, ``brute_topk``,
``max_ulp``) so that no change to the program can move it; nothing here
imports the program.  ``dtype`` lets the control compute the same scan in
a lower precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BRUTE_BLOCK = 1 << 13          # rows per step of the scan


def pairwise_sq_dist(x: jax.Array, q: jax.Array) -> jax.Array:
    """Squared distances ``[Q, B]`` between queries ``q [Q, L]`` and rows
    ``x [B, L]``, in the fixed pairwise order, in the inputs' dtype."""
    s = x[None, :, :] - q[:, None, :]
    s = jnp.maximum(s * s, 0)
    width = 1 << (s.shape[-1] - 1).bit_length()
    s = jnp.pad(s, ((0, 0), (0, 0), (0, width - s.shape[-1])))
    while width > 1:
        width //= 2
        s = s[..., :width] + s[..., width:]
    return s[..., 0]


@functools.partial(jax.jit, static_argnames=("k", "block", "dtype"))
def brute_topk(rows: jax.Array, queries: jax.Array, *, k: int,
               block: int = BRUTE_BLOCK, dtype=jnp.float32):
    """Exact k-NN of ``queries`` over every row of ``rows``: distances
    block by block (:func:`pairwise_sq_dist`) and a running top-k.
    Returns (squared distances ``[Q, k]`` float32, row indices ``[Q, k]``
    int32); ties go to the lower row index."""
    n = rows.shape[0]
    nb = -(-n // block)
    if nb * block != n:
        rows = jnp.pad(rows, ((0, nb * block - n), (0, 0)))
    nq = queries.shape[0]
    q = queries.astype(dtype)

    def step(i, carry):
        best_d, best_i = carry
        x = jax.lax.dynamic_slice_in_dim(rows, i * block, block)
        d = pairwise_sq_dist(x.astype(dtype), q).astype(jnp.float32)
        idx = i * block + jnp.arange(block, dtype=jnp.int32)
        d = jnp.where(idx[None, :] < n, d, jnp.inf)
        cat_d = jnp.concatenate([best_d, d], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(idx, (nq, block))], axis=1)
        neg, sel = jax.lax.top_k(-cat_d, k)
        return -neg, jnp.take_along_axis(cat_i, sel, axis=1)

    init = (jnp.full((nq, k), jnp.inf, jnp.float32),
            jnp.full((nq, k), -1, jnp.int32))
    return jax.lax.fori_loop(0, nb, step, init)


def brute_topk_blocked(rows: jax.Array, queries: np.ndarray, *, k: int,
                       qblock: int = 16, dtype=jnp.float32):
    """:func:`brute_topk` over a long query list, ``qblock`` queries per
    pass (one compiled program for every pass), on the host as numpy."""
    queries = np.asarray(queries, np.float32)
    nq = len(queries)
    pad = -nq % qblock
    qs = np.concatenate([queries, np.repeat(queries[-1:], pad, 0)])
    out_d, out_i = [], []
    for s in range(0, len(qs), qblock):
        d, i = brute_topk(rows, jnp.asarray(qs[s:s + qblock]), k=k,
                          dtype=dtype)
        out_d.append(np.asarray(d))
        out_i.append(np.asarray(i))
    return (np.concatenate(out_d)[:nq],
            np.concatenate(out_i).astype(np.int64)[:nq])


@jax.jit
def _row_dists(rows, ids, queries):
    x = rows[jnp.clip(ids, 0, rows.shape[0] - 1)]          # [Q, k, L]
    s = x - queries[:, None, :]
    s = jnp.maximum(s * s, 0.0)
    width = s.shape[-1]
    while width > 1:
        width //= 2
        s = s[..., :width] + s[..., width:]
    return s[..., 0]


def distances_of(rows: jax.Array, queries: np.ndarray,
                 ids: np.ndarray) -> np.ndarray:
    """Reference squared distance ``[Q, k]`` from each query to each row
    its answer names (``inf`` where the id is out of range).  The series
    length must be a power of two."""
    ids = np.asarray(ids, np.int64)
    d = np.asarray(_row_dists(rows, jnp.asarray(ids, jnp.int32),
                              jnp.asarray(queries, jnp.float32)))
    return np.where((ids >= 0) & (ids < rows.shape[0]), d, np.inf)


def ulps(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Distance in float32 ulps between finite non-negative floats
    (their int32 views are then monotone); a non-finite value on either
    side reads 2**31."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    ai = a.view(np.int32).astype(np.int64)
    bi = b.view(np.int32).astype(np.int64)
    out = np.abs(ai - bi)
    return np.where(np.isfinite(a) & np.isfinite(b), out, 1 << 31)


def max_ulp(a: np.ndarray, b: np.ndarray) -> int:
    """Largest :func:`ulps` between two arrays (0 for empty ones)."""
    u = ulps(a, b)
    return int(u.max()) if u.size else 0
