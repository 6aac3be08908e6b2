"""Pallas TPU kernel: fused bit-unpack + batched SIMS lower bound.

Segment format v3 stores SAX codes bit-packed at ``b`` bits per symbol
(``ceil(w*b/8)`` bytes per row instead of ``w``) — that is what makes
hot leaves cheap enough to keep device-resident.  Scanning them with the
existing batched kernel would need a host-side (or separate-launch)
unpack, touching ``w/pw``x more HBM than the data actually occupies.
This kernel fuses the unpack into the scan: packed code tiles stream
HBM -> VMEM at their *packed* width and are expanded to symbols in
registers, so the bandwidth win of packing survives into the scan
itself.

TPU adaptation notes:
  * Packed rows stream transposed, ``[ceil(w*b/8) + 1, block_n]``: every
    packed byte column is one lane-dense row of the tile.
  * Symbol extraction is a static Python loop over the ``w`` columns —
    each symbol spans at most two adjacent bytes (b <= 8), so one
    16-bit window shift per column; no gathers, and the loop unrolls
    into straight-line VPU code at trace time.
  * One zero byte is padded onto every packed row so the two-byte
    window never reads past the row, including at ``b == 8``.
  * Each extracted ``[1, block_n]`` column feeds the column-at-a-time
    bound accumulation of ``mindist_batch.bound_tile`` — same tiles,
    same ``[Q, block_n]`` output layout — so the two kernels stay
    interchangeable behind ``ops.mindist_batch``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .mindist_batch import bound_tile

__all__ = ["unpack_mindist_batch_pallas"]


def _kernel(packed_ref, qpaa_ref, lower_ref, upper_ref, out_ref, *,
            w: int, b: int, scale: float):
    pk = packed_ref[...]                               # [pw + 1, bn] int32

    def code_row(j):
        bl, sh = (j * b) // 8, (j * b) % 8
        window = (pk[bl:bl + 1, :] << 8) | pk[bl + 1:bl + 2, :]
        return (window >> (16 - sh - b)) & ((1 << b) - 1)

    out_ref[...] = bound_tile(code_row, qpaa_ref[...], lower_ref[...],
                              upper_ref[...], w=w,
                              scale=scale).astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("w", "b", "scale", "block_n",
                                    "interpret"))
def unpack_mindist_batch_pallas(q_paas: jax.Array, packed: jax.Array,
                                lower: jax.Array, upper: jax.Array, *,
                                w: int, b: int, scale: float,
                                block_n: int = 256,
                                interpret: Optional[bool] = None
                                ) -> jax.Array:
    """Batched squared mindist over *packed* codes.

    q_paas ``[Q, w]``, packed ``[N, ceil(w*b/8)]`` uint8 -> ``[Q, N]``,
    bit-identical to ``mindist_batch_pallas`` on the decoded rows.
    ``lower``/``upper`` are the per-code region bounds (``[2**b]``,
    +-inf replaced by large finite sentinels by the caller).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, pw = packed.shape
    nq = q_paas.shape[0]
    card = lower.shape[0]
    n_pad = -(-n // block_n) * block_n
    # pad rows for the grid AND one zero byte per row for the two-byte
    # extraction window
    packed_t = jnp.pad(packed.astype(jnp.int32),
                       ((0, n_pad - n), (0, 1))).T
    grid = (n_pad // block_n,)
    out = pl.pallas_call(
        functools.partial(_kernel, w=w, b=b, scale=float(scale)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((pw + 1, block_n), lambda i: (0, i)),
            pl.BlockSpec((nq, w), lambda i: (0, 0)),
            pl.BlockSpec((card, 1), lambda i: (0, 0)),
            pl.BlockSpec((card, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((nq, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((nq, n_pad), jnp.float32),
        interpret=interpret,
    )(packed_t, q_paas.astype(jnp.float32),
      lower[:, None].astype(jnp.float32),
      upper[:, None].astype(jnp.float32))
    return out[:, :n]
