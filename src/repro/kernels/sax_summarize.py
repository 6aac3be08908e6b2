"""Fused PAA + SAX quantization Pallas kernel (index-construction hot pass).

Bulk-loading (Algorithms 2/3/6) starts with a full scan of the raw file that
computes each series' summarization.  At TPU scale this is the
bandwidth-dominant pass: ``N × L`` float32 in, ``N × w`` codes out (a ~64x
reduction at the paper's L=256, w=16).  Fusing PAA (segment means) with the
breakpoint quantization keeps the raw tile in VMEM for exactly one pass.

Quantization is expressed as a compare-and-count against the breakpoint
table (``code = #{breakpoints <= paa}``) — a dense VPU reduction over the
``2**b - 1`` table entries instead of a searchsorted gather.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["sax_summarize_pallas"]


def _kernel(x_ref, bps_ref, paa_ref, codes_ref, *, segments: int):
    x = x_ref[...]                                   # [bn, L] f32
    bps = bps_ref[...]                               # [1, card-1]
    bn, L = x.shape
    seg_len = L // segments
    paa = jnp.mean(x.reshape(bn, segments, seg_len), axis=-1)   # [bn, w]
    # code = count of breakpoints <= value  (searchsorted side='right')
    ge = paa[:, :, None] >= bps[0][None, None, :]    # [bn, w, card-1]
    codes = jnp.sum(ge.astype(jnp.int32), axis=-1)
    paa_ref[...] = paa.astype(jnp.float32)
    codes_ref[...] = codes


@functools.partial(jax.jit, static_argnames=("segments", "block_n",
                                             "interpret"))
def sax_summarize_pallas(x: jax.Array, bps: jax.Array, *, segments: int,
                         block_n: int = 256, interpret: Optional[bool] = None):
    """Raw series ``[N, L]`` -> (paa ``[N, w]`` f32, codes ``[N, w]`` int32)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, L = x.shape
    nb = bps.shape[0]
    n_pad = -(-n // block_n) * block_n
    x_p = jnp.pad(x.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    grid = (n_pad // block_n,)
    paa, codes = pl.pallas_call(
        functools.partial(_kernel, segments=segments),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, L), lambda i: (i, 0)),
            pl.BlockSpec((1, nb), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_n, segments), lambda i: (i, 0)),
            pl.BlockSpec((block_n, segments), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_pad, segments), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, segments), jnp.int32),
        ),
        interpret=interpret,
    )(x_p, bps[None, :].astype(jnp.float32))
    return paa[:n], codes[:n]
