"""Batched Euclidean-distance Pallas kernel (verification / brute force).

Exact search verifies unpruned candidates against the query with true
squared ED; the brute-force baseline (paper Sec. 2) is the same kernel run
over the whole dataset.  Bandwidth-bound: ``block_n × L`` floats per tile,
one multiply-add per element, reduced on the VPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import summarization as S

__all__ = ["batch_euclid_pallas"]


def _kernel(q_ref, x_ref, out_ref):
    q = q_ref[...]                                  # [1, L]
    x = x_ref[...]                                  # [bn, L]
    out_ref[...] = S.sum_sq(x - q).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def batch_euclid_pallas(query: jax.Array, series: jax.Array, *,
                        block_n: int = 256,
                        interpret: Optional[bool] = None) -> jax.Array:
    """query ``[L]``, series ``[N, L]`` -> squared ED ``[N]`` float32.

    ``interpret=None`` resolves through the backend dispatch policy
    (compiled on TPU, interpret mode elsewhere) instead of the old
    hard-coded ``True``, which silently ran the interpreter even where
    the compiled kernel was available — prefer calling through
    :func:`repro.kernels.ops.batch_euclid`, which picks the mode.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, L = series.shape
    n_pad = -(-n // block_n) * block_n
    x_p = jnp.pad(series.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    grid = (n_pad // block_n,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L), lambda i: (0, 0)),
            pl.BlockSpec((block_n, L), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.float32),
        interpret=interpret,
    )(query[None, :].astype(jnp.float32), x_p)
    return out[:n]
