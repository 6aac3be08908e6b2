"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels are tested against
(`tests/test_kernels.py` sweeps shapes/dtypes and asserts allclose).  They are
also the production fallback on non-TPU backends.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import keys as K
from ..core import summarization as S

__all__ = ["mindist_ref", "mindist_batch_ref", "sax_summarize_ref",
           "zorder_ref", "batch_euclid_ref", "batch_euclid_multi_ref",
           "batch_euclid_blocked_ref", "ED_BLOCK",
           "scan_verify_ref", "unpack_codes_ref",
           "mindist_batch_packed_ref", "mesh_scan_ref"]


def mindist_ref(q_paa: jax.Array, codes: jax.Array, lower: jax.Array,
                upper: jax.Array, scale: float) -> jax.Array:
    """Squared iSAX lower bound; q_paa [w], codes [N, w] -> [N] float32."""
    lb = lower[codes.astype(jnp.int32)]
    ub = upper[codes.astype(jnp.int32)]
    q = q_paa[None, :]
    below = jnp.where(q < lb, lb - q, 0.0)
    above = jnp.where(q > ub, q - ub, 0.0)
    d = below + above
    return scale * jnp.sum(d * d, axis=-1).astype(jnp.float32)


def mindist_batch_ref(q_paas: jax.Array, codes: jax.Array, lower: jax.Array,
                      upper: jax.Array, scale: float) -> jax.Array:
    """Batched lower bound; q_paas [Q, w], codes [N, w] -> [Q, N] float32.

    One pass over the codes amortized across the whole query batch — the
    semantic ground truth for the batched SIMS scan kernel.
    """
    lb = lower[codes.astype(jnp.int32)]              # [N, w]
    ub = upper[codes.astype(jnp.int32)]
    q = q_paas[:, None, :]                           # [Q, 1, w]
    below = jnp.where(q < lb[None], lb[None] - q, 0.0)
    above = jnp.where(q > ub[None], q - ub[None], 0.0)
    d = below + above
    return scale * jnp.sum(d * d, axis=-1).astype(jnp.float32)


def unpack_codes_ref(packed: jax.Array, *, w: int, b: int) -> jax.Array:
    """Packed ``[N, ceil(w*b/8)]`` uint8 rows -> ``[N, w]`` int32 codes.

    Symbol ``j`` occupies bits ``[j*b, (j+1)*b)`` of its row, MSB-first
    (the v3 segment layout of :mod:`repro.storage.packing`).  For b <= 8
    a symbol spans at most two adjacent bytes, so each column extraction
    is one 16-bit window shift — exact integer ops, bit-identical to the
    numpy decoder.  Padding one zero byte keeps the second-byte index in
    range for every symbol, including ``b == 8`` (where this degenerates
    to the identity).
    """
    pk = packed.astype(jnp.int32)
    pk = jnp.pad(pk, ((0, 0), (0, 1)))
    cols = []
    for j in range(w):
        bl, sh = (j * b) // 8, (j * b) % 8
        window = (pk[:, bl] << 8) | pk[:, bl + 1]
        cols.append((window >> (16 - sh - b)) & ((1 << b) - 1))
    return jnp.stack(cols, axis=1)


def mindist_batch_packed_ref(q_paas: jax.Array, packed: jax.Array,
                             lower: jax.Array, upper: jax.Array, *,
                             scale: float, w: int, b: int) -> jax.Array:
    """Fused oracle: unpack v3 code rows, then the batched lower bound.

    q_paas [Q, w], packed [N, ceil(w*b/8)] -> [Q, N] float32, bit-equal
    to ``mindist_batch_ref`` on the decoded codes (the parity guarantee
    the packed executor fast path rests on).
    """
    return mindist_batch_ref(q_paas, unpack_codes_ref(packed, w=w, b=b),
                             lower, upper, scale)


def sax_summarize_ref(x: jax.Array, bps: jax.Array, segments: int):
    """Raw series [N, L] -> (paa [N, w] f32, codes [N, w] int32)."""
    p = S.paa(x.astype(jnp.float32), segments)
    codes = jnp.searchsorted(bps, p, side="right").astype(jnp.int32)
    return p, codes


def zorder_ref(codes: jax.Array, *, w: int, b: int) -> jax.Array:
    """SAX codes [N, w] -> z-order keys [N, n_words] uint32."""
    return K.interleave_codes(codes, w=w, b=b)


def batch_euclid_ref(query: jax.Array, series: jax.Array) -> jax.Array:
    """query [L], series [N, L] -> squared ED [N] float32."""
    diff = series.astype(jnp.float32) - query.astype(jnp.float32)[None, :]
    return S.sum_sq(diff)


def batch_euclid_multi_ref(queries: jax.Array,
                           series: jax.Array) -> jax.Array:
    """queries [Q, L], series [N, L] -> squared ED [Q, N] float32."""
    diff = (series.astype(jnp.float32)[None, :, :]
            - queries.astype(jnp.float32)[:, None, :])
    return S.sum_sq(diff)


# rows per blocked-ED step: the naive [Q, N, L] difference tensor is
# ~1 GB at serving scale and memory bandwidth kills the scan; blocking
# the row axis keeps each [Q, BLOCK, L] intermediate cache-sized
# (several times faster on CPU hosts)
ED_BLOCK = 512


def batch_euclid_blocked_ref(queries: jax.Array,
                             series: jax.Array) -> jax.Array:
    """``batch_euclid_multi_ref`` computed in fixed [Q, ED_BLOCK, L]
    row blocks (zero-padded tail, trimmed after).

    Always blocked — even when N <= ED_BLOCK — so the compiled
    reduction body is one fixed shape and the bits are invariant to N:
    the same row scanned under any shard/device partitioning (which
    changes only the local N) produces the same distance word.  That
    invariance is what lets the mesh launch match the single-device
    oracle and the sharded index keep shard-count bit-parity.
    """
    n = series.shape[0]
    pad = (-n) % ED_BLOCK
    sp = jnp.pad(series, ((0, pad), (0, 0)))
    blocks = sp.reshape(-1, ED_BLOCK, series.shape[-1])
    out = jax.lax.map(
        lambda blk: batch_euclid_multi_ref(queries, blk), blocks)
    return out.transpose(1, 0, 2).reshape(queries.shape[0], -1)[:, :n]


def scan_verify_ref(queries: jax.Array, q_paas: jax.Array,
                    codes: jax.Array, raw: jax.Array,
                    lower: jax.Array, upper: jax.Array,
                    bound: jax.Array, dead: jax.Array, *,
                    scale: float, k: int):
    """Fused SIMS scan+verify oracle: lower bound, bound-masked Euclidean
    verification, and top-k in one pass.

    queries [Q, L], q_paas [Q, w], codes [N, w], raw [N, L],
    bound [Q] (rows with mindist >= bound are abandoned before the
    Euclidean distance is consulted), dead [N] (nonzero = row filtered
    out, e.g. by a window cut).  Returns (top-k dists [Q, k] with inf
    padding, top-k row indices [Q, k] int32 with -1 padding, verified
    counts [Q] int32, union int32 — distinct rows live for ANY query,
    the batch-level ``candidates`` accounting).
    """
    md = mindist_batch_ref(q_paas, codes, lower, upper, scale)   # [Q, N]
    live = (md < bound[:, None]) & (dead[None, :] == 0)
    ed = batch_euclid_multi_ref(queries, raw)                    # [Q, N]
    ed = jnp.where(live, ed, jnp.inf)
    neg, idx = jax.lax.top_k(-ed, k)
    d = -neg
    idx = jnp.where(jnp.isfinite(d), idx.astype(jnp.int32), -1)
    counts = jnp.sum(live, axis=1).astype(jnp.int32)
    union = jnp.sum(jnp.any(live, axis=0)).astype(jnp.int32)
    return d, idx, counts, union


def mesh_scan_ref(queries: jax.Array, q_paas: jax.Array,
                  codes: jax.Array, raw: jax.Array,
                  ids: jax.Array, ts: jax.Array, ts_min: jax.Array,
                  bound: jax.Array, lower: jax.Array, upper: jax.Array,
                  *, scale: float, k: int):
    """Oracle for the device-resident sharded scan: global top-k over the
    stacked shard columns, as if every shard lived on one device.

    queries [Q, L], q_paas [Q, w], codes [S, cap, w], raw [S, cap, L],
    ids [S, cap] int32 (-1 marks padding rows), ts [S, cap] int32,
    ts_min [S] int32 per-shard visibility cut (use INT32_MIN to disable),
    bound [Q] per-query strict best-so-far from the buffer pool.
    Returns (dists [Q, k] inf-padded, global ids [Q, k] int32 with -1
    padding, counts [S, Q] int32 — rows verified per shard per query).

    The ``shard_map`` launch must match this bit-for-bit: its per-device
    partial top-k + all-gather merge selects the same distance *values*
    (no re-arithmetic), so only tie ordering can differ — measure-zero
    on real-valued series data.
    """
    s, cap = ids.shape
    dead = (ids < 0) | (ts < ts_min[:, None])
    codes_f = codes.reshape(s * cap, codes.shape[-1])
    raw_f = raw.reshape(s * cap, raw.shape[-1])
    dead_f = dead.reshape(s * cap).astype(jnp.int32)
    md = mindist_batch_ref(q_paas, codes_f, lower, upper, scale)
    live = (md < bound[:, None]) & (dead_f[None, :] == 0)
    ed = jnp.where(live, batch_euclid_blocked_ref(queries, raw_f),
                   jnp.inf)
    neg, idx = jax.lax.top_k(-ed, k)
    d = -neg
    ids_f = ids.reshape(s * cap)
    out_ids = jnp.where(jnp.isfinite(d), ids_f[idx], -1)
    counts = jnp.transpose(
        jnp.sum(live.reshape(-1, s, cap), axis=2)).astype(jnp.int32)
    return d, out_ids, counts
