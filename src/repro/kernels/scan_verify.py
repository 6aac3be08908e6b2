"""Fused Pallas TPU kernel for the SIMS scan+verify hot loop.

The pre-fusion pipeline round-trips three times per leaf group:
``mindist_batch`` (one kernel launch) -> host-side mask -> gather of the
unpruned rows -> ``batch_euclid`` (another launch) -> host-side top-k
merge.  Serving traffic pays that latency per probe micro-batch.  This
kernel fuses the whole chain over one streaming pass: each ``[block_n]``
tile of the code AND raw columns is read HBM -> VMEM exactly once, the
iSAX lower bound masks the Euclidean verification in-register
(early-abandoning: a row whose bound cannot beat the per-query bsf
never contributes arithmetic to the top-k), and a running per-query
top-k is carried across grid steps on device — only ``[Q, k]`` answers
ever cross back to the host.

TPU adaptation notes:
  * The query tiles (raw + PAA), the region-bound tables, and the
    running top-k accumulators use constant index maps, so they stay
    VMEM-resident across the entire N-grid.
  * Codes stream transposed (``[w, block_n]``) into the column-at-a-time
    bound of ``mindist_batch.bound_tile``, so the bound never builds a
    ``[block_n, w, 2**b]`` one-hot (gathers are hostile to the VPU, and
    that cube alone is 4 MiB of f32 at the paper's widths).
  * The verification loops over chunks of ``_query_chunk`` queries, so
    only one ``[Qc, block_n, L]`` difference tile is live at a time and
    Q=64 at L=256 fits the 16 MiB scoped VMEM.  Each chunk's distances go
    to a ``[Q, block_n]`` VMEM scratch; the wrapper pads the query batch
    to whole chunks (padded queries have bound -inf, so nothing is live
    for them).  Every chunk sums its squares with
    ``summarization.sum_sq``, in the fixed order the eager chain uses,
    so a row's distance has the same bits on both paths.
  * The top-k merge is gather-free selection: k unrolled rounds of
    min/argmin + one-hot masking over the ``[Q, k + block_n]``
    concatenation — no sort network, no dynamic indexing.
  * Grid steps execute sequentially on TPU, so read-modify-write on the
    constant-mapped output tiles is the standard accumulation pattern.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import summarization as S
from .mindist_batch import bound_tile

__all__ = ["scan_verify_pallas"]

# VMEM bytes of one query chunk's [Qc, block_n, L] difference tile
_ED_TILE_BYTES = 1 << 21


def _query_chunk(nq: int, block_n: int, L: int) -> int:
    """Queries per verification chunk: as many as keep the difference
    tile under ``_ED_TILE_BYTES``, and a multiple of the 8-row sublane
    tile whenever the batch needs more than one chunk."""
    qc = max(1, _ED_TILE_BYTES // (block_n * L * 4))
    return nq if nq <= qc else max(8, qc // 8 * 8)


def _kernel(codes_ref, raw_ref, q_ref, qpaa_ref, lower_ref, upper_ref,
            bound_ref, dead_ref, outd_ref, outi_ref, cnt_ref, uni_ref,
            ed_ref, *, w: int, scale: float, k: int, n: int, block_n: int,
            qc: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        outd_ref[...] = jnp.full(outd_ref.shape, jnp.inf, jnp.float32)
        outi_ref[...] = jnp.full(outi_ref.shape, -1, jnp.int32)
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, jnp.int32)
        uni_ref[...] = jnp.zeros(uni_ref.shape, jnp.int32)

    codes = codes_ref[...]                            # [w, bn] int32
    bn = codes.shape[1]
    md = bound_tile(lambda j: codes[j:j + 1, :], qpaa_ref[...],
                    lower_ref[...], upper_ref[...], w=w,
                    scale=scale)                      # [Q, bn]

    rowid = i * block_n + jax.lax.broadcasted_iota(jnp.int32, (bn,), 0)
    valid = (rowid < n) & (dead_ref[...][0] == 0)
    bound = bound_ref[...][0]                         # [Q]
    live = (md < bound[:, None]) & valid[None, :]     # [Q, bn]
    cnt_ref[...] = cnt_ref[...] + \
        jnp.sum(live, axis=1).astype(jnp.int32)[None, :]
    uni_ref[...] = uni_ref[...] + \
        jnp.sum(jnp.any(live, axis=0)).astype(jnp.int32)

    # early-abandoning verify: rows the bound pruned contribute inf only
    x = raw_ref[...]                                  # [bn, L]
    nq = q_ref.shape[0]

    def chunk(c, carry):
        c0 = pl.multiple_of(c * qc, qc)
        qq = q_ref[pl.ds(c0, qc), :]                  # [Qc, L]
        ed_ref[pl.ds(c0, qc), :] = S.sum_sq(x[None, :, :] - qq[:, None, :])
        return carry

    jax.lax.fori_loop(0, nq // qc, chunk, 0)
    ed = jnp.where(live, ed_ref[...], jnp.inf)        # [Q, bn]

    # merge the tile into the running top-k (gather-free selection)
    cat_d = jnp.concatenate([outd_ref[...], ed], axis=1)   # [Q, k+bn]
    cat_i = jnp.concatenate(
        [outi_ref[...], jnp.broadcast_to(rowid[None, :], ed.shape)],
        axis=1)
    cols = jax.lax.broadcasted_iota(jnp.int32, cat_d.shape, 1)
    sel_d, sel_i = [], []
    for _ in range(k):
        dmin = jnp.min(cat_d, axis=1)                 # [Q]
        amin = jnp.argmin(cat_d, axis=1).astype(jnp.int32)
        hit = cols == amin[:, None]
        imin = jnp.sum(jnp.where(hit, cat_i, 0), axis=1)
        sel_d.append(dmin)
        sel_i.append(jnp.where(jnp.isfinite(dmin), imin, -1))
        cat_d = jnp.where(hit, jnp.inf, cat_d)
    outd_ref[...] = jnp.stack(sel_d, axis=1)
    outi_ref[...] = jnp.stack(sel_i, axis=1).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("scale", "k", "block_n", "interpret"))
def scan_verify_pallas(queries: jax.Array, q_paas: jax.Array,
                       codes: jax.Array, raw: jax.Array,
                       lower: jax.Array, upper: jax.Array,
                       bound: jax.Array, dead: jax.Array, *,
                       scale: float, k: int = 1, block_n: int = 256,
                       interpret: Optional[bool] = None):
    """Fused scan+verify: queries ``[Q, L]``, q_paas ``[Q, w]``, codes
    ``[N, w]``, raw ``[N, L]``, bound ``[Q]``, dead ``[N]`` ->
    (top-k dists ``[Q, k]``, top-k indices ``[Q, k]`` int32 with -1
    padding, verified counts ``[Q]`` int32, union-verified rows int32).

    ``interpret=None`` resolves through the backend dispatch policy:
    compiled on TPU, interpret mode elsewhere (CPU validation of the TPU
    kernel body) — never hard-code it at a call site; go through
    :func:`repro.kernels.ops.scan_verify`.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, w = codes.shape
    nq0, L = queries.shape
    qc = _query_chunk(nq0, block_n, L)
    nq = -(-nq0 // qc) * qc
    if nq != nq0:
        queries = jnp.pad(queries, ((0, nq - nq0), (0, 0)))
        q_paas = jnp.pad(q_paas, ((0, nq - nq0), (0, 0)))
        bound = jnp.pad(bound, (0, nq - nq0), constant_values=-jnp.inf)
    card = lower.shape[0]
    n_pad = -(-n // block_n) * block_n
    codes_t = jnp.pad(codes.astype(jnp.int32), ((0, n_pad - n), (0, 0))).T
    raw_p = jnp.pad(raw.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    dead_p = jnp.pad(dead.astype(jnp.int32), (0, n_pad - n),
                     constant_values=1)
    grid = (n_pad // block_n,)
    out_d, out_i, cnt, uni = pl.pallas_call(
        functools.partial(_kernel, w=w, scale=float(scale), k=k,
                          n=n, block_n=block_n, qc=qc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((w, block_n), lambda i: (0, i)),
            pl.BlockSpec((block_n, L), lambda i: (i, 0)),
            pl.BlockSpec((nq, L), lambda i: (0, 0)),
            pl.BlockSpec((nq, w), lambda i: (0, 0)),
            pl.BlockSpec((card, 1), lambda i: (0, 0)),
            pl.BlockSpec((card, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, nq), lambda i: (0, 0)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_specs=(
            pl.BlockSpec((nq, k), lambda i: (0, 0)),
            pl.BlockSpec((nq, k), lambda i: (0, 0)),
            pl.BlockSpec((1, nq), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((nq, k), jnp.float32),
            jax.ShapeDtypeStruct((nq, k), jnp.int32),
            jax.ShapeDtypeStruct((1, nq), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ),
        scratch_shapes=[pltpu.VMEM((nq, block_n), jnp.float32)],
        interpret=interpret,
    )(codes_t, raw_p, queries.astype(jnp.float32),
      q_paas.astype(jnp.float32),
      lower[:, None].astype(jnp.float32),
      upper[:, None].astype(jnp.float32),
      bound[None, :].astype(jnp.float32), dead_p[None, :])
    return out_d[:nq0], out_i[:nq0], cnt[0, :nq0], uni[0, 0]
