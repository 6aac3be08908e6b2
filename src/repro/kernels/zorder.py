"""Z-order bit-interleave Pallas kernel (the paper's Algorithm 1 on TPU).

The interleave permutes ``w*b`` bits per series into ``n_words`` uint32
words, MSB-first.  It is a fixed bit permutation, so the kernel is a fully
unrolled sequence of shift/and/or vector ops over a ``[block_n]`` lane tile —
pure VPU work at one pass over the codes.  Fused after
:mod:`repro.kernels.sax_summarize` this makes index construction a single
HBM round trip: raw series in, sortable keys out.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.keys import n_key_words

__all__ = ["zorder_pallas"]


def _kernel(codes_ref, out_ref, *, w: int, b: int, n_words: int):
    codes = codes_ref[...].astype(jnp.uint32)        # [bn, w]
    bn = codes.shape[0]
    words = [jnp.zeros((bn,), jnp.uint32) for _ in range(n_words)]
    for p in range(w * b):
        i, j = divmod(p, w)                          # significance, segment
        bit = (codes[:, j] >> jnp.uint32(b - 1 - i)) & jnp.uint32(1)
        word_idx, bit_idx = divmod(p, 32)
        words[word_idx] = words[word_idx] | (bit << jnp.uint32(31 - bit_idx))
    out_ref[...] = jnp.stack(words, axis=1)


@functools.partial(jax.jit, static_argnames=("w", "b", "block_n",
                                             "interpret"))
def zorder_pallas(codes: jax.Array, *, w: int, b: int, block_n: int = 1024,
                  interpret: Optional[bool] = None) -> jax.Array:
    """SAX codes ``[N, w]`` -> z-order keys ``[N, n_words]`` uint32."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = codes.shape[0]
    nw = n_key_words(w, b)
    n_pad = -(-n // block_n) * block_n
    codes_p = jnp.pad(codes.astype(jnp.int32), ((0, n_pad - n), (0, 0)))
    grid = (n_pad // block_n,)
    out = pl.pallas_call(
        functools.partial(_kernel, w=w, b=b, n_words=nw),
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_n, nw), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, nw), jnp.uint32),
        interpret=interpret,
    )(codes_p)
    return out[:n]
