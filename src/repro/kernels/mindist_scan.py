"""Pallas TPU kernel for the SIMS lower-bound scan — the paper's hot loop.

Exact search (Algorithm 5) is bottlenecked by computing the iSAX mindist
between the query and *every* in-memory summarization: a pure
bandwidth-bound streaming pass over ``N × w`` one-byte codes.  The paper
parallelizes this across CPU cores; on TPU we stream code tiles
HBM -> VMEM with an explicit BlockSpec grid and evaluate the bound on the
VPU, with the (tiny) region tables resident in VMEM across the whole grid.

TPU adaptation notes:
  * The per-code region-bound lookup is a gather on CPU; gathers are hostile
    to the TPU vector unit, so the kernel re-expresses the lookup as a
    one-hot contraction against the ``[2**b]`` bound tables (compare +
    select + reduce over the cardinality axis) — dense, layout-friendly,
    and exactly equivalent.
  * Block shape: ``(block_n, w)`` codes with ``w``-minor layout; ``block_n``
    defaults to 512 so the working set (codes tile + one-hot accumulators)
    stays well under VMEM while the N-grid amortizes table residency.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["mindist_pallas"]


def _kernel(codes_ref, qpaa_ref, lower_ref, upper_ref, out_ref, *,
            card: int, scale: float):
    codes = codes_ref[...].astype(jnp.int32)          # [bn, w]
    q = qpaa_ref[...]                                  # [1, w]
    lower = lower_ref[...]                             # [1, card]
    upper = upper_ref[...]
    bn, w = codes.shape
    # one-hot table lookup: VPU compare+select+reduce, no gather
    iota = jax.lax.broadcasted_iota(jnp.int32, (bn, w, card), 2)
    onehot = (codes[:, :, None] == iota)
    lb = jnp.sum(jnp.where(onehot, lower[0][None, None, :], 0.0), axis=-1)
    ub = jnp.sum(jnp.where(onehot, upper[0][None, None, :], 0.0), axis=-1)
    below = jnp.maximum(lb - q, 0.0)
    above = jnp.maximum(q - ub, 0.0)
    d = below + above
    out_ref[...] = (scale * jnp.sum(d * d, axis=-1)).astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_n", "interpret"))
def mindist_pallas(q_paa: jax.Array, codes: jax.Array, lower: jax.Array,
                   upper: jax.Array, *, scale: float, block_n: int = 512,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Squared mindist lower bounds: codes ``[N, w]`` -> ``[N]`` float32.

    ``lower``/``upper`` are the per-code region bounds (``[2**b]``, +-inf at
    the extremes replaced by large finite sentinels by the caller — the
    kernel is inf-safe but XLA:TPU prefers finite tables).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, w = codes.shape
    card = lower.shape[0]
    n_pad = -(-n // block_n) * block_n
    codes_p = jnp.pad(codes, ((0, n_pad - n), (0, 0)))
    grid = (n_pad // block_n,)
    out = pl.pallas_call(
        functools.partial(_kernel, card=card, scale=float(scale)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, w), lambda i: (i, 0)),
            pl.BlockSpec((1, w), lambda i: (0, 0)),
            pl.BlockSpec((1, card), lambda i: (0, 0)),
            pl.BlockSpec((1, card), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.float32),
        interpret=interpret,
    )(codes_p.astype(jnp.int32), q_paa[None, :].astype(jnp.float32),
      lower[None, :].astype(jnp.float32), upper[None, :].astype(jnp.float32))
    return out[:n]
