"""Fused index-construction Pallas kernel: raw series -> z-order keys in
ONE HBM round trip.

Bulk-loading reads N x L floats and emits N x n_words keys (a ~256x
reduction at L=256).  Running PAA, SAX quantization, and the bit
interleave as one kernel keeps the raw tile resident in VMEM for exactly
one pass — the unfused pipeline reads/writes the intermediate codes to HBM
twice.  This is the construction-side analogue of the mindist fusion: both
ends of the paper's pipeline become single-pass streaming kernels.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.keys import n_key_words

__all__ = ["fused_build_pallas"]


def _kernel(x_ref, bps_ref, paa_ref, codes_ref, keys_ref, *,
            segments: int, bits: int, n_words: int):
    x = x_ref[...]                                   # [bn, L]
    bps = bps_ref[...]                               # [1, card-1]
    bn, L = x.shape
    seg_len = L // segments
    paa = jnp.mean(x.reshape(bn, segments, seg_len), axis=-1)
    ge = paa[:, :, None] >= bps[0][None, None, :]
    codes = jnp.sum(ge.astype(jnp.int32), axis=-1)   # [bn, w]
    ucodes = codes.astype(jnp.uint32)
    words = [jnp.zeros((bn,), jnp.uint32) for _ in range(n_words)]
    for p in range(segments * bits):
        i, j = divmod(p, segments)
        bit = (ucodes[:, j] >> jnp.uint32(bits - 1 - i)) & jnp.uint32(1)
        wi, bi = divmod(p, 32)
        words[wi] = words[wi] | (bit << jnp.uint32(31 - bi))
    paa_ref[...] = paa.astype(jnp.float32)
    codes_ref[...] = codes
    keys_ref[...] = jnp.stack(words, axis=1)


@functools.partial(jax.jit, static_argnames=("segments", "bits", "block_n",
                                             "interpret"))
def fused_build_pallas(x: jax.Array, bps: jax.Array, *, segments: int,
                       bits: int, block_n: int = 256,
                       interpret: Optional[bool] = None):
    """Raw ``[N, L]`` -> (paa f32, codes i32, keys u32) in one pass."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, L = x.shape
    nb = bps.shape[0]
    nw = n_key_words(segments, bits)
    n_pad = -(-n // block_n) * block_n
    x_p = jnp.pad(x.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    grid = (n_pad // block_n,)
    paa, codes, keys = pl.pallas_call(
        functools.partial(_kernel, segments=segments, bits=bits,
                          n_words=nw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, L), lambda i: (i, 0)),
            pl.BlockSpec((1, nb), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_n, segments), lambda i: (i, 0)),
            pl.BlockSpec((block_n, segments), lambda i: (i, 0)),
            pl.BlockSpec((block_n, nw), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_pad, segments), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, segments), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, nw), jnp.uint32),
        ),
        interpret=interpret,
    )(x_p, bps[None, :].astype(jnp.float32))
    return paa[:n], codes[:n], keys[:n]
