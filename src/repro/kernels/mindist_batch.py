"""Pallas TPU kernel for the *batched* SIMS lower-bound scan.

The single-query scan (``mindist_scan.py``) is bandwidth-bound: the VPU is
mostly idle waiting on the ``N x w`` code stream from HBM.  Serving traffic
gives us a lever the paper's single-query setting does not: amortize one
pass over the in-memory summarizations across a whole *batch* of queries.
Each ``[block_n, w]`` code tile is streamed HBM -> VMEM exactly once and
evaluated against the full ``[Q, w]`` query-PAA tile, multiplying the
arithmetic intensity of the scan by Q at unchanged memory traffic.

TPU adaptation notes:
  * The query-PAA tile and the ``[2**b]`` region-bound tables use constant
    index maps, so they stay VMEM-resident across the entire N-grid — only
    code tiles and output tiles move per grid step.
  * Codes stream transposed, ``[w, block_n]``: every SAX column is one
    lane-dense row, so the per-code region lookup is a one-hot
    compare+select+sublane-reduce over a ``[2**b, block_n]`` tile
    (gathers are hostile to the VPU) that lands directly in the
    ``[1, block_n]`` lane layout of the output.
  * The bound is accumulated one SAX column at a time into a
    ``[Q, block_n]`` tile (:func:`bound_tile`).  Every intermediate is
    then lane-dense and independent of ``w``: a ``[block_n, w, 2**b]``
    one-hot or a ``[Q, block_n, w]`` distance cube (``w`` padded to 128
    lanes) would overflow the 16 MiB scoped VMEM at the paper's widths
    (w=16, b=8) already at Q=8.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["mindist_batch_pallas", "bound_tile"]


def bound_tile(code_row: Callable[[int], jax.Array], q: jax.Array,
               lower: jax.Array, upper: jax.Array, *, w: int,
               scale: float) -> jax.Array:
    """Squared iSAX lower bound of one code tile against every query:
    ``[Q, block_n]``.

    ``code_row(j)`` gives SAX column ``j`` of the tile as ``[1, block_n]``
    int32; ``q`` is the ``[Q, w]`` query-PAA tile; ``lower``/``upper`` are
    the ``[2**b, 1]`` region-bound tables.  Shared by every kernel that
    prices a code tile (batched scan, packed scan, fused scan+verify).
    """
    card = lower.shape[0]
    acc = None
    for j in range(w):
        c = code_row(j)                                     # [1, bn]
        onehot = c == jax.lax.broadcasted_iota(jnp.int32,
                                               (card, c.shape[1]), 0)
        lb = jnp.sum(jnp.where(onehot, lower, 0.0), axis=0, keepdims=True)
        ub = jnp.sum(jnp.where(onehot, upper, 0.0), axis=0, keepdims=True)
        qj = q[:, j:j + 1]                                  # [Q, 1]
        d = jnp.maximum(lb - qj, 0.0) + jnp.maximum(qj - ub, 0.0)
        acc = d * d if acc is None else acc + d * d
    return scale * acc


def _kernel(codes_ref, qpaa_ref, lower_ref, upper_ref, out_ref, *,
            w: int, scale: float):
    codes = codes_ref[...]                             # [w, bn] int32
    out_ref[...] = bound_tile(lambda j: codes[j:j + 1, :], qpaa_ref[...],
                              lower_ref[...], upper_ref[...], w=w,
                              scale=scale).astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_n", "interpret"))
def mindist_batch_pallas(q_paas: jax.Array, codes: jax.Array,
                         lower: jax.Array, upper: jax.Array, *,
                         scale: float, block_n: int = 256,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Batched squared mindist: q_paas ``[Q, w]``, codes ``[N, w]`` -> ``[Q, N]``.

    ``lower``/``upper`` are the per-code region bounds (``[2**b]``, +-inf at
    the extremes replaced by large finite sentinels by the caller).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, w = codes.shape
    nq = q_paas.shape[0]
    card = lower.shape[0]
    n_pad = -(-n // block_n) * block_n
    codes_t = jnp.pad(codes.astype(jnp.int32), ((0, n_pad - n), (0, 0))).T
    grid = (n_pad // block_n,)
    out = pl.pallas_call(
        functools.partial(_kernel, w=w, scale=float(scale)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((w, block_n), lambda i: (0, i)),
            pl.BlockSpec((nq, w), lambda i: (0, 0)),
            pl.BlockSpec((card, 1), lambda i: (0, 0)),
            pl.BlockSpec((card, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((nq, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((nq, n_pad), jnp.float32),
        interpret=interpret,
    )(codes_t, q_paas.astype(jnp.float32),
      lower[:, None].astype(jnp.float32), upper[:, None].astype(jnp.float32))
    return out[:, :n]
