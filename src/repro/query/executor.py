"""Plan execution: seed -> leaf-masked lower-bound scan -> verify.

One executor serves every backend: sorted partitions are scanned
leaf-granularly (surviving leaves only, cheapest fence bound first —
skip-sequential SIMS), whether the codes live on device
(``CoconutTree``) or on disk behind an mmap (``Segment``, with every
byte that crosses the storage boundary charged to ``IOStats``);
unsorted frozen buffers are brute-force verified with the same
Euclidean kernel, so answer *distances* are bit-identical regardless of
how rows are partitioned — the invariant the streaming and sharded
engines are built on.

Two loops scan a sorted partition's surviving leaves, and both give the
same answer bits (ids included) on the same rows:

* Device partitions (``backend == "device"``: a ``CoconutTree`` or an
  LSM run) with the default bound and no ``scan_mode`` run in *leaf
  waves* (:func:`_scan_waves`).  The k-NN pool and its k-th-best bound
  stay on the device for the whole partition; each wave of ``W`` leaves
  is one bound program (:func:`wave_bound`: mindists straight from the
  tree's contiguous code rows, the live mask against the device bound)
  and one verify program per block of ``C`` live rows
  (:func:`wave_verify`: distances of the live rows only, merged into the
  pool by a stable top-k), with one small read a wave and one read of
  the result.  ``W`` and ``C`` follow from the shapes so that either
  program's intermediates stay under ``_WAVE_BYTES`` (256 MiB).
* Everything else keeps the host leaf loop (:func:`_scan_leaf_group`, a
  bound and a verify program per leaf group, the pool on the host):
  mmap segments and tiered partitions, whose ``IOStats`` charges are
  made leaf by leaf as the loop reads, an injected ``mindist_fn``, and
  the fused ``scan_mode``.  The budgeted drain (:mod:`.approx`) calls
  the same group scan.

The default scan path keeps the eager kernel chain
(:func:`repro.core.summarization.mindist_sq_batch` lower bounds +
:func:`repro.core.summarization.euclidean_sq_batch` verification) whose
bits every entry point historically returned; ``scan_mode`` opts into
the fused :mod:`repro.kernels.scan_verify` Pallas kernel (one pass:
bound + masked verify + on-device top-k), which is validated against
the eager chain in the kernel tests.

The host's share of a probe is named by three spans inside its stages:
``exec.launch`` over the preparation and enqueueing of a device program
(row gathers, padding, host-to-device copies, the dispatch), ``exec.sync``
over every read of a device result (:func:`~repro.query.merger.to_host`,
counted in ``SearchStats.device_syncs``), and ``exec.pool`` over every
host-side pool update.
"""
from __future__ import annotations

import functools
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import summarization as S
from ..obs import record_search, span as _span
from .merger import KnnPool, SearchStats, to_host
from .partition import Partition
from .planner import ScanEntry, ScanPlan, build_plan

__all__ = ["execute", "exact_knn", "buffer_topk", "device_queries",
           "query_paas"]


def _pad_batch(x: np.ndarray) -> np.ndarray:
    """``x`` with its rows padded to the next power of two by repeating
    the last one.  Programs are compiled per shape, and a shard or run is
    probed with whatever subset of the batch its bounds let through, so
    unpadded every subset size would compile programs of its own."""
    n = x.shape[0]
    return np.pad(x, ((0, (1 << (n - 1).bit_length()) - n), (0, 0)),
                  mode="edge")


def device_queries(queries: np.ndarray, q_paas: np.ndarray):
    """The query batch and its PAAs as the device programs see them,
    padded by :func:`_pad_batch`.  The scan keeps its per-query state for
    the real rows only (``KnnPool``) and drops the padded rows of every
    result."""
    import jax.numpy as jnp
    return jnp.asarray(_pad_batch(queries)), jnp.asarray(_pad_batch(q_paas))


def query_paas(queries: np.ndarray, segments: int,
               stats: Optional[SearchStats] = None) -> np.ndarray:
    """Host PAAs ``[Q, w]`` of a query batch, computed on the padded
    batch (:func:`_pad_batch`).  They are the planner's input, so their
    device read is traced as a ``plan`` span of its own."""
    import jax.numpy as jnp
    with _span("plan", stage="paa", queries=queries.shape[0]):
        paas = S.paa(jnp.asarray(_pad_batch(queries)), segments)
        return to_host(paas, stats)[:queries.shape[0]]


def buffer_topk(queries_j, rows: np.ndarray, offs: np.ndarray, k: int,
                io=None, stats: Optional[SearchStats] = None
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Brute-force per-query ``[Q, k]`` pools over unsorted rows with
    the verification kernel — THE buffer-scan contract (stable sort,
    (inf, -1) padding) shared by the exact executor and the snapshot's
    approximate path, so the distance bits always match a post-flush
    search of the same rows."""
    import jax.numpy as jnp
    nq = queries_j.shape[0]
    best_d = np.full((nq, k), np.inf, np.float32)
    best_off = np.full((nq, k), -1, np.int64)
    if len(rows) == 0:
        return best_d, best_off
    if io is not None:
        io.seq_read(len(rows))
    with _span("exec.launch"):
        d = S.euclidean_sq_batch(queries_j, jnp.asarray(rows))
    d = to_host(d, stats)                                   # [Q, M]
    sel = np.argsort(d, axis=1, kind="stable")[:, :k]
    take = min(k, d.shape[1])
    best_d[:, :take] = np.take_along_axis(d, sel, axis=1)[:, :take]
    best_off[:, :take] = offs[sel][:, :take]
    return best_d, best_off


def _scan_buffer(entry: ScanEntry, queries_j, k: int,
                 pool: KnnPool, stats: SearchStats, io) -> None:
    part = entry.partition
    rows = part.buffer_raw()
    offs = part.report_ids()
    if entry.ts_min is not None:
        ts = part.timestamps()
        keep = np.nonzero(ts >= entry.ts_min)[0]
        rows, offs = rows[keep], offs[keep]
    if len(rows) == 0:
        return
    new_d, new_off = buffer_topk(queries_j, rows, offs, k, io=io,
                                 stats=stats)
    with _span("exec.pool"):
        pool.update_batch(new_d[:pool.nq], new_off[:pool.nq])
    stats.buffer_rows += len(rows)
    stats.candidates_per_query += len(rows)


def _seed_sorted(entry: ScanEntry, queries_j, q_paas_j,
                 pool: KnnPool, stats: SearchStats, *, radius_leaves: int,
                 io) -> Tuple[Optional[np.ndarray], np.ndarray, np.ndarray]:
    """Seed the pool from the leaves around each query's z-order slot
    (the Algorithm-4 probe).  Returns ``(alive, offs_all, idx0)`` for
    the scan that follows.  Shared by the exact path and the budgeted
    drain so seed distance bits are identical by construction."""
    import jax.numpy as jnp
    part = entry.partition
    nq = pool.nq
    alive = None
    if entry.ts_min is not None:
        ts = part.timestamps()
        if ts is not None:
            alive = ts >= entry.ts_min
    offs_all = part.report_ids()
    idx0 = part.seed_window(queries_j, radius_leaves=radius_leaves, io=io,
                            q_paas=q_paas_j, stats=stats)
    # canonical bits: seed distances add in the verifier's fixed order
    # (``S.sum_sq``) so returned values never depend on partitioning —
    # one gather + one batched op for the whole pool
    with _span("exec.launch"):
        rows0 = part.series_rows(idx0.reshape(-1), io=io)
        rows0 = jnp.asarray(rows0).reshape(idx0.shape + (-1,))  # [Q, C, L]
        d0 = S.sq_dist(rows0, queries_j[:, None, :])
    d0 = np.asarray(to_host(d0, stats), np.float32)
    if alive is not None:
        d0 = np.where(alive[idx0], d0, np.inf)
        offs0 = np.where(alive[idx0], offs_all[idx0], -1)
    else:
        offs0 = offs_all[idx0]
    with _span("exec.pool"):
        for qi in range(nq):
            pool.update(qi, d0[qi], offs0[qi])
    return alive, offs_all, idx0[:nq]


def _leaves_per_group(chunk: int, nq: int, leaf: int) -> int:
    """Leaves per verification group: bound the [Q, B, L] intermediate
    (rows-per-chunk scales down with batch size — Q=64 x 4096 x L floats
    thrashes host memory)."""
    eff_chunk = min(chunk, max(64, 32768 // nq))
    return max(1, eff_chunk // leaf)


def _verify_rows(n: int) -> int:
    """Row count a verification block is padded to: the next power of
    two, at least 64.  Which rows survive the bound is data-dependent, so
    unpadded blocks would compile the verification anew for nearly every
    leaf group; padded, a scan reuses a handful of programs.  Each row's
    distance is its own reduction, so padding never changes its bits."""
    return max(64, 1 << (int(n) - 1).bit_length())


def _bound_rows(part: Partition, idx: np.ndarray, io, *, packed: bool):
    """Code rows (packed or full width) for ``idx``, padded with copies
    of the last row to :func:`_verify_rows` rows.  Reads are charged as
    for ``idx`` alone: device and tier-cached partitions are read (and
    charged) leaf by leaf, and the padding only repeats a row of a leaf
    already read; a bare mmap charges per row, so there the block is
    padded after the read."""
    npad = _verify_rows(len(idx)) - len(idx)
    if part.backend == "device" or part.tiers is not None:
        idx, npad = np.pad(idx, (0, npad), mode="edge"), 0
    blk = (part.codes_rows_packed if packed else part.codes_rows)(idx, io=io)
    if npad:
        blk = np.pad(np.asarray(blk), ((0, npad), (0, 0)), mode="edge")
    return blk


def _scan_leaf_group(entry: ScanEntry, queries_j, q_paas_j,
                     grp: np.ndarray, k: int, pool: KnnPool,
                     stats: SearchStats, alive, offs_all,
                     leaf_mark, union_mark, io, mindist_fn,
                     fused: Optional[str]) -> Tuple[int, int]:
    """Bound + verify one sorted group of leaf indices against the pool.

    Returns ``(live_pairs, nbytes)`` where ``nbytes`` counts the code
    rows streamed plus the raw rows fetched for verification — computed
    from shapes so the charge is identical across backends (the currency
    of the ``max_bytes`` budget)."""
    import jax.numpy as jnp
    part = entry.partition
    nq = pool.nq
    leaf = part.leaf_size
    row_idx = (grp[:, None] * leaf
               + np.arange(leaf)[None, :]).reshape(-1)
    row_idx = row_idx[row_idx < part.n]
    nbytes = len(row_idx) * part.cfg.segments
    if fused is not None:
        with _span("exec.launch"):
            codes_blk = part.codes_rows(row_idx, io=io)
        t0 = time.perf_counter()
        with _span("verify", rows=len(row_idx), fused=True) as vsp:
            before = stats.candidates
            live_pairs = _verify_fused(
                entry, queries_j, q_paas_j, codes_blk, row_idx, k, pool,
                stats, alive, offs_all, leaf_mark, union_mark, io, fused)
            vsp.set(candidates=stats.candidates - before,
                    raw_bytes=len(row_idx) * part.cfg.series_len * 4)
        stats.add_timing("verify", (time.perf_counter() - t0) * 1e3)
        # the fused kernel streams the whole group's raw rows (that IS
        # the fusion), so the group charges every row's raw bytes
        return live_pairs, nbytes + len(row_idx) * part.cfg.series_len * 4
    # packed fast path: when the partition stores v3 packed codes and
    # the lower bound is the default kernel, hand the stored-form rows
    # straight to the fused unpack+mindist kernel — no host-side decode,
    # and device-promoted hot leaves skip the host->device copy too.
    # Both bound paths compute identical bits, so answers never depend
    # on which one ran.
    # the bound runs over the rows padded like a verification block
    # (edge rows, dropped below), so groups cut short by a run's last
    # leaf reuse the programs of whole ones
    nr = len(row_idx)
    with _span("exec.launch"):
        if (part.is_packed
                and getattr(mindist_fn, "_coconut_default_mindist", False)):
            from ..kernels import ops
            packed_blk = _bound_rows(part, row_idx, io, packed=True)
            md = ops.mindist_batch_packed(q_paas_j,
                                          jnp.asarray(packed_blk), part.cfg)
        else:
            codes_blk = _bound_rows(part, row_idx, io, packed=False)
            if part.backend != "device":
                codes_blk = jnp.asarray(codes_blk)
            md = mindist_fn(q_paas_j, codes_blk)
    md = to_host(md, stats)[:nq, :nr]                         # [Q, B]
    live = md < pool.bound()[:, None]
    if alive is not None:
        live &= alive[row_idx][None, :]
    live_pairs = int(live.sum())
    keep = live.any(axis=0)
    if not keep.any():
        return live_pairs, nbytes
    block = row_idx[keep]
    mask = live[:, keep]
    t0 = time.perf_counter()
    with _span("verify", rows=len(block)) as vsp:
        nb = len(block)
        pad = _verify_rows(nb) - nb
        with _span("exec.launch"):
            if part.backend == "device":
                # pad the device gather itself (edge rows, dropped below)
                rows = part.series_rows(
                    np.pad(block, (0, pad), mode="edge"), io=io)
                if io is not None:
                    io.seq_read(nb)
            else:
                rows = np.pad(np.asarray(part.series_rows(block, io=io)),
                              ((0, pad), (0, 0)))
            dd = S.euclidean_sq_batch(queries_j, jnp.asarray(rows))
        dd = to_host(dd, stats)[:nq, :nb]                       # [Q, B]
        nbytes += len(block) * part.cfg.series_len * 4
        stats.candidates += len(block)
        union_mark[block // leaf] = True
        with _span("exec.pool"):
            for qi in range(nq):
                m = mask[qi]
                if not m.any():
                    continue
                stats.candidates_per_query[qi] += int(m.sum())
                leaf_mark[qi, block[m] // leaf] = True
                pool.update(qi, dd[qi][m], offs_all[block[m]])
        vsp.set(candidates=len(block),
                raw_bytes=len(block) * part.cfg.series_len * 4)
    stats.add_timing("verify", (time.perf_counter() - t0) * 1e3)
    return live_pairs, nbytes


def _leaf_groups(surv: np.ndarray, per_group: int) -> List[np.ndarray]:
    """The scan's leaf groups in visiting order: ``surv`` (cheapest bound
    first) cut into groups of ``per_group``, each in leaf order so its
    rows are read sequentially.  Both scan loops visit leaves in this
    order, so rows tied at the k-th distance resolve alike on both."""
    return [np.sort(surv[g:g + per_group])
            for g in range(0, len(surv), per_group)]


# Bytes the intermediates of one leaf wave may take in either of its
# programs, counted as if nothing were fused: the bound's [Qp, rows, w]
# float32 differences with each row's mindist and live flag, and the
# verify's [Qp, C, L] float32 differences with its C raw rows.
_WAVE_BYTES = 256 << 20


def _pow2_floor(n: int) -> int:
    return 1 << (max(1, int(n)).bit_length() - 1)


def _wave_shape(qp: int, part: Partition) -> Tuple[int, int]:
    """``(W, C)`` for a device partition probed with ``qp`` padded
    queries: leaves a wave bounds and rows a verify block reads, powers
    of two under :data:`_WAVE_BYTES` (W no more than the partition's
    leaves rounded up to a power of two).  They follow from the shapes
    alone, so a partition compiles one bound and one verify program per
    query padding."""
    cfg = part.cfg
    rows = min(part.leaf_size, part.n)
    width = _pow2_floor(_WAVE_BYTES // (qp * rows * (4 * cfg.segments + 5)))
    width = min(width, 1 << (part.n_leaves - 1).bit_length())
    cap = _pow2_floor(_WAVE_BYTES // ((qp + 1) * cfg.series_len * 4))
    return width, min(cap, width * rows)


def _wave_rows(order, j, n: int, leaf_size: int, width: int):
    """Rows ``[W, R]`` of wave ``j`` of the leaf ``order``, the first row
    of each block, and whether each row belongs to its leaf.  A leaf is
    read as ``R = min(leaf_size, n)`` contiguous rows; the last leaf's
    block starts early enough to end at row ``n``, and its rows of the
    leaf before are not its own."""
    r = min(leaf_size, n)
    first = lax.dynamic_slice_in_dim(order, j * width, width) * leaf_size
    start = jnp.minimum(first, n - r)
    rows = start[:, None] + jnp.arange(r, dtype=start.dtype)[None, :]
    return rows, start, rows >= first[:, None]


def _pack_queries(live):
    """A ``[Qp, B]`` mask as bit words ``[ceil(Qp / 32), B]`` uint32:
    bit ``q % 32`` of word ``q // 32`` is query ``q``'s flag."""
    qp, b = live.shape
    nw = -(-qp // 32)
    bits = jnp.pad(live, ((0, 32 * nw - qp), (0, 0))).reshape(nw, 32, b)
    shift = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    return (bits.astype(jnp.uint32) << shift).sum(axis=1, dtype=jnp.uint32)


def _unpack_queries(words, qp: int):
    """The ``[qp, C]`` mask of bit words ``[ceil(qp / 32), C]``."""
    q = jnp.arange(qp, dtype=jnp.uint32)
    return ((words[q // 32] >> (q % 32)[:, None]) & 1).astype(bool)


@functools.partial(jax.jit, static_argnames=("cfg", "leaf_size", "width"))
def wave_bound(codes, q_paas, order, thresh, j, n_order, best_d, ext,
               alive, *, cfg: S.SummaryConfig, leaf_size: int, width: int):
    """Bound one wave: the mindists of its rows against the pool's bound.

    Returns the live mask (a row of a real leaf, alive, its mindist
    strictly below the query's bound) as bit words ``[ceil(Qp / 32),
    W * R]`` (:func:`_pack_queries`), and the wave's counts, int32
    ``[Qp + 2, W]``: live rows per query and leaf, live rows per leaf for
    any query, and the stop flag in ``[Qp + 1, 0]``.  The stop flag is
    set when ``thresh[j]``, the least bound of any leaf from this wave
    on, reaches every query's bound: then no row of this wave or a later
    one can be live, and the mask is empty."""
    n, w = codes.shape
    rows, start, own = _wave_rows(order, j, n, leaf_size, width)
    r = rows.shape[1]

    def take(a):                         # [W, R, ...]: contiguous blocks
        return jax.vmap(lambda s: lax.dynamic_slice_in_dim(a, s, r))(start)

    ok = own & ((j * width + jnp.arange(width)) < n_order)[:, None]
    if alive is not None:
        ok &= take(alive)
    md = S.mindist_sq_batch(q_paas, take(codes).reshape(-1, w), cfg)
    bound = jnp.minimum(best_d[:, -1], ext)
    go = thresh[j] < jnp.max(bound)
    live = (md < bound[:, None]) & (ok.reshape(-1) & go)[None, :]
    per = live.reshape(-1, width, r).sum(axis=-1, dtype=jnp.int32)
    union = live.any(axis=0).reshape(width, r).sum(axis=-1,
                                                   dtype=jnp.int32)
    stop = jnp.zeros(width, jnp.int32).at[0].set((~go).astype(jnp.int32))
    return (_pack_queries(live),
            jnp.concatenate([per, union[None], stop[None]]))


@functools.partial(jax.jit, static_argnames=("leaf_size", "width", "cap"))
def wave_verify(tree, queries, order, j, live, block, best_d, best_pos, *,
                leaf_size: int, width: int, cap: int):
    """Verify block ``block`` of a wave's live rows and merge it into
    the pool.

    The rows live for some query are numbered in wave order; the block
    reads slots ``[block * C, (block + 1) * C)`` of them (slots past the
    last re-read the last live row and are dead), so no raw row the mask
    rules out is read.  Distances are ``S.euclidean_sq_batch``'s bits.
    The merge is ``merge_topk``'s rule: pool entries first, then the
    block in wave order, a row already in the pool (its seed put it
    there) dropped, and a stable top-k keeping the earlier entry on equal
    distances.  ``best_pos`` holds rows of this partition, ``-2 - i``
    for entry ``i`` of the pool the partition started from, and -1 for
    an empty slot.  ``live`` is :func:`wave_bound`'s bit words: gathered
    by row as words, the mask keeps the distances' ``[Qp, C]`` layout
    (a gather of a ``[Qp, B]`` mask's columns made the compiler lay the
    distances out queries-minor, eight times their size on the TPU)."""
    rows = _wave_rows(order, j, tree.n, leaf_size, width)[0].reshape(-1)
    csum = jnp.cumsum((live != 0).any(axis=0), dtype=jnp.int32)
    slot = block * cap + jnp.arange(cap, dtype=jnp.int32)
    at = jnp.searchsorted(csum, jnp.minimum(slot, csum[-1] - 1) + 1)
    r = rows[at]
    d = S.euclidean_sq_batch(queries, tree.series(r))
    words = jnp.stack([live[i][at] for i in range(live.shape[0])])
    m = _unpack_queries(words, queries.shape[0]) & (slot < csum[-1])[None]
    m &= ~jnp.any(r[None, :, None] == best_pos[:, None, :], axis=-1)
    all_d = jnp.concatenate([best_d, jnp.where(m, d, jnp.inf)], axis=1)
    all_p = jnp.concatenate([best_pos, jnp.where(m, r, -1)], axis=1)
    _, sel = lax.top_k(-all_d, best_d.shape[1])
    return (jnp.take_along_axis(all_d, sel, axis=1),
            jnp.take_along_axis(all_p, sel, axis=1))


@jax.jit
def _wave_result(best_d, best_pos):
    """The device pool as one array, for one read: ``[2, Qp, k]``
    float32, the slots' bits in the second plane."""
    return jnp.stack([best_d, lax.bitcast_convert_type(best_pos,
                                                       jnp.float32)])


def _pool_slots(best_off: np.ndarray, offs_all: np.ndarray,
                idx0: np.ndarray) -> np.ndarray:
    """The host pool's entries as :func:`wave_verify` holds them: the
    row of this partition an entry names (only the seed window ``idx0``
    can have put one there), else ``-2 - i`` for entry ``i``, and -1
    for an empty slot."""
    eq = offs_all[idx0][:, :, None] == best_off[:, None, :]  # [Q, C, k]
    rows = np.take_along_axis(idx0, eq.argmax(axis=1), axis=1)
    slots = np.where(eq.any(axis=1), rows, -2 - np.arange(best_off.shape[1]))
    return np.where(best_off < 0, -1, slots).astype(np.int32)


def _scan_waves(entry: ScanEntry, queries_j, q_paas_j, order: np.ndarray,
                pool: KnnPool, stats: SearchStats, alive, offs_all,
                idx0: np.ndarray, leaf_mark, union_mark, io) -> int:
    """Scan a device partition's leaves ``order`` in waves, the pool on
    the device.  Returns the live (query, row) pairs.

    Wave ``j + 1``'s bound program is enqueued before wave ``j``'s
    counts are read, so the device always holds work while the host
    reads; it bounds against the pool as it stood before wave ``j``'s
    verify, an older and looser bound, which only verifies more rows.
    Each wave's verify merges before the next one's, so rows enter the
    pool in visiting order, as in the host loop.  Leaves of waves the
    stop flag ends count as pruned."""
    part = entry.partition
    tree = part.source
    nq, qp, k = pool.nq, queries_j.shape[0], pool.k
    leaf, n, seg_bytes = part.leaf_size, part.n, part.cfg.segments
    row_bytes = part.cfg.series_len * 4
    width, cap = _wave_shape(qp, part)
    n_waves = -(-len(order) // width)
    # the device arrays are sized for a scan of every leaf, so the shapes
    # (and the compiled programs) do not depend on how many leaves the
    # fence bounds pruned
    n_slots = -(-part.n_leaves // width)
    # the least bound of any leaf from each wave on: the stop test
    lb_min = entry.leaf_bounds[:, order].min(axis=0)
    rest = np.minimum.accumulate(lb_min[::-1])[::-1]
    with _span("exec.launch"):
        order_j = jnp.asarray(np.pad(order, (0, n_slots * width - len(order)),
                                     mode="edge").astype(np.int32))
        thresh_j = jnp.asarray(np.pad(rest[::width], (0, n_slots - n_waves),
                                      mode="edge"))
        # the padded queries' pools copy the last query's, as their rows do
        best_d = jnp.asarray(_pad_batch(pool.best_d))
        best_pos = jnp.asarray(_pad_batch(
            _pool_slots(pool.best_off, offs_all, idx0)))
        ext = jnp.asarray(_pad_batch(pool.ext[:, None])[:, 0])
        alive_j = None if alive is None else jnp.asarray(alive)

        def bound(j):
            return wave_bound(tree.codes, q_paas_j, order_j, thresh_j, j,
                              len(order), best_d, ext, alive_j,
                              cfg=part.cfg, leaf_size=leaf, width=width)
        nxt = bound(0)
    stats.device_scans += 1
    live_pairs = 0
    for j in range(n_waves):
        live, counts = nxt
        if j + 1 < n_waves:
            with _span("exec.launch"):
                nxt = bound(j + 1)
        counts = to_host(counts, stats)
        stats.device_waves += 1
        if counts[qp + 1, 0]:
            stats.leaves_pruned += len(order) - j * width
            break
        leaves = order[j * width:(j + 1) * width]
        per = counts[:nq, :len(leaves)]
        union = counts[qp, :len(leaves)]
        n_live = int(union.sum())
        stats.leaves_scanned += len(leaves)
        stats.scan_bytes += (int(np.minimum(leaf, n - leaves * leaf).sum())
                             * seg_bytes + n_live * row_bytes)
        if n_live == 0:
            continue
        t0 = time.perf_counter()
        with _span("verify", rows=n_live) as vsp:
            with _span("exec.launch"):
                for b in range(-(-n_live // cap)):
                    best_d, best_pos = wave_verify(
                        tree, queries_j, order_j, j, live, b, best_d,
                        best_pos, leaf_size=leaf, width=width, cap=cap)
            vsp.set(candidates=n_live, raw_bytes=n_live * row_bytes)
        stats.add_timing("verify", (time.perf_counter() - t0) * 1e3)
        if io is not None:
            io.seq_read(n_live)
        stats.candidates += n_live
        stats.candidates_per_query += per.sum(axis=1)
        live_pairs += int(per.sum())
        leaf_mark[:, leaves] |= per > 0
        union_mark[leaves] |= union > 0
    with _span("exec.launch"):
        res = _wave_result(best_d, best_pos)
    res = to_host(res, stats)[:, :nq]
    with _span("exec.pool"):
        d, pos = res[0], res[1].view(np.int32)
        seeded = np.take_along_axis(pool.best_off,
                                    np.clip(-2 - pos, 0, k - 1), axis=1)
        off = np.where(pos >= 0, offs_all[np.maximum(pos, 0)], seeded)
        pool.best_d = np.array(d, np.float32)
        pool.best_off = np.where(pos == -1, -1, off).astype(np.int64)
    return live_pairs


def _scan_sorted(entry: ScanEntry, queries_j, q_paas_j, k: int,
                 pool: KnnPool, stats: SearchStats, *,
                 radius_leaves: int, chunk: int, io, mindist_fn,
                 scan_mode: Optional[str],
                 label: str = "") -> int:
    """Seed + leaf-skip scan + verify one sorted partition.  Returns the
    number of live (query, row) pairs the lower bound could not prune."""
    part = entry.partition
    nq = pool.nq
    leaf = part.leaf_size
    # the fused kernel streams the whole leaf group's raw rows (that is
    # the fusion); on mmap partitions that would fetch pruned rows' raw
    # bytes from disk, so fusion stays a device-backend path
    fused = scan_mode if part.backend == "device" else None

    with _span("seed", radius_leaves=radius_leaves):
        alive, offs_all, idx0 = _seed_sorted(
            entry, queries_j, q_paas_j, pool, stats,
            radius_leaves=radius_leaves, io=io)

    # -- leaf-granular pruning against the fence bounds --------------------
    # (the seed probe above always runs — the external bsf and the fence
    # bounds prune the SCAN, never the seeds, matching the historical
    # run-chaining contract)
    with _span("prune", leaves=part.n_leaves) as psp:
        bound = pool.bound()
        if np.all(entry.part_bound >= bound):  # whole-partition fast path
            stats.partitions_pruned += 1
            stats.leaves_pruned += part.n_leaves
            psp.set(leaves_pruned=part.n_leaves, whole_partition=True)
            return 0
        lb = entry.leaf_bounds                                # [Q, n_leaves]
        surv = np.nonzero((lb < bound[:, None]).any(axis=0))[0]
        stats.leaves_pruned += lb.shape[1] - len(surv)
        psp.set(leaves_pruned=lb.shape[1] - len(surv),
                leaves_surviving=len(surv))
        if len(surv) == 0:
            stats.partitions_pruned += 1
            psp.set(whole_partition=True)
            return 0
        # cheapest leaves first: the bound tightens fastest, pruning the rest
        surv = surv[np.argsort(lb[:, surv].min(axis=0), kind="stable")]

    groups = _leaf_groups(surv, _leaves_per_group(chunk, nq, leaf))
    leaf_mark = np.zeros((nq, lb.shape[1]), bool)
    union_mark = np.zeros(lb.shape[1], bool)
    if (part.backend == "device" and part.tiers is None and fused is None
            and getattr(mindist_fn, "_coconut_default_mindist", False)):
        live_pairs = _scan_waves(entry, queries_j, q_paas_j,
                                 np.concatenate(groups), pool, stats,
                                 alive, offs_all, idx0, leaf_mark,
                                 union_mark, io)
    else:
        stats.leaves_scanned += len(surv)
        live_pairs = 0
        for grp in groups:
            live, nbytes = _scan_leaf_group(
                entry, queries_j, q_paas_j, grp, k, pool, stats, alive,
                offs_all, leaf_mark, union_mark, io, mindist_fn, fused)
            live_pairs += live
            stats.scan_bytes += nbytes
    stats.leaves_touched += int(union_mark.sum())
    stats.leaves_per_query += leaf_mark.sum(axis=1)
    if label:
        stats.touch_leaves(label, np.nonzero(union_mark)[0])
    return live_pairs


def _verify_fused(entry: ScanEntry, queries_j, q_paas_j, codes_blk,
                  row_idx: np.ndarray, k: int, pool: KnnPool,
                  stats: SearchStats, alive, offs_all,
                  leaf_mark, union_mark, io, scan_mode: str) -> int:
    """Fused-kernel verification of one leaf group: bound + masked
    Euclidean + on-device top-k in a single pass (TPU serving path).

    ``candidates``/``candidates_per_query`` match the eager chain (the
    kernel reports per-query and union live counts); leaf attribution is
    top-k-grained — only the rows that survive into the pool mark their
    leaves, since the full live mask never leaves the device."""
    import jax.numpy as jnp
    from ..kernels import ops
    part = entry.partition
    nq = pool.nq
    bound = pool.bound()
    bound = np.pad(bound, (0, queries_j.shape[0] - nq), mode="edge")
    if alive is not None:
        dead = ~alive[row_idx]
    else:
        dead = None
    with _span("exec.launch"):
        rows = part.series_rows(row_idx, io=io)
        d, li, counts, union = ops.scan_verify(
            queries_j, q_paas_j, jnp.asarray(codes_blk), jnp.asarray(rows),
            jnp.asarray(bound), part.cfg, k=min(k, len(row_idx)),
            mode=scan_mode,
            dead=None if dead is None else jnp.asarray(dead))
    d = np.asarray(to_host(d, stats), np.float32)[:nq]
    li = to_host(li, stats)[:nq]
    counts = to_host(counts, stats)[:nq]
    live = 0
    with _span("exec.pool"):
        for qi in range(nq):
            stats.candidates_per_query[qi] += int(counts[qi])
            live += int(counts[qi])
            fin = np.isfinite(d[qi])
            if not fin.any():
                continue
            rows_qi = row_idx[li[qi][fin]]
            leaf_mark[qi, rows_qi // part.leaf_size] = True
            union_mark[rows_qi // part.leaf_size] = True
            pool.update(qi, d[qi][fin], offs_all[rows_qi])
    stats.candidates += int(to_host(union, stats))
    if io is not None:
        io.seq_read(len(row_idx))
    return live


def execute(plan: ScanPlan, queries, *, k: int = 1,
            bsf: Optional[np.ndarray] = None,
            radius_leaves: int = 1, chunk: int = 4096,
            io=None, mindist_fn=None,
            scan_mode: Optional[str] = None,
            stats: Optional[SearchStats] = None
            ) -> Tuple[np.ndarray, np.ndarray, SearchStats]:
    """Run a :class:`ScanPlan` and return (dists ``[Q, k]``, ids
    ``[Q, k]``, :class:`SearchStats`).

    ``bsf``: optional ``[Q]`` per-query external bounds (LSM run / shard
    chaining) — they prune the scan but are never returned as answers.
    ``mindist_fn``: injectable lower-bound kernel with the batched
    signature ``(q_paas [Q, w], codes [B, w]) -> [Q, B]`` (defaults to
    :func:`repro.core.summarization.mindist_sq_batch`; the Pallas kernel
    drops in via ``repro.kernels.ops.mindist_batch``).
    ``scan_mode``: None (eager chain, the bit-canonical default) or a
    kernel dispatch mode (``"pallas"`` / ``"interpret"`` / ``"jnp"``)
    for the fused scan+verify kernel.  ``"mesh"`` normalizes to None:
    the device-resident mesh launch is orchestrated ABOVE this seam (in
    the sharded fan-out) and this executor IS its threaded fallback, so
    a mesh request that reaches here runs the canonical eager chain.
    ``stats``: the accounting to continue (the device reads that
    summarized and planned the batch are already in it); a fresh one
    when None.
    """
    if scan_mode == "mesh":
        scan_mode = None
    queries_np = np.atleast_2d(np.asarray(queries, np.float32))
    nq = queries_np.shape[0]
    queries_j, q_paas_j = device_queries(queries_np, plan.q_paas)
    pool = KnnPool(nq, k, ext=bsf)
    stats = SearchStats() if stats is None else stats
    stats.exact, stats.queries = True, nq
    stats.candidates_per_query = np.zeros(nq, np.int64)
    stats.leaves_per_query = np.zeros(nq, np.int64)
    live_pairs = 0
    total_rows = 0
    t_scan = time.perf_counter()
    for pi, entry in enumerate(plan.entries):
        part = entry.partition
        label = f"p{pi}:{part.kind}"
        if not part.is_sorted:
            with _span("scan", part=label, rows=part.n) as sp:
                before_rows = stats.buffer_rows
                _scan_buffer(entry, queries_j, k, pool, stats, io)
                sp.set(buffer_rows=stats.buffer_rows - before_rows)
            continue
        if mindist_fn is None:
            cfg = part.cfg
            part_mindist = lambda qp, c: S.mindist_sq_batch(qp, c, cfg)
            # marks the bound as the default kernel, which the packed
            # scan fast path is bit-equal to — injected bounds disable it
            part_mindist._coconut_default_mindist = True
        else:
            part_mindist = mindist_fn
        total_rows += part.n
        pruned_before = stats.partitions_pruned
        # scan-span attrs are deltas of the SAME stats counters, so the
        # per-span numbers sum to the SearchStats totals by construction
        b_scanned, b_pruned = stats.leaves_scanned, stats.leaves_pruned
        b_bytes, b_cand = stats.scan_bytes, stats.candidates
        with _span("scan", part=label, rows=part.n,
                   leaves=part.n_leaves) as sp:
            live_pairs += _scan_sorted(
                entry, queries_j, q_paas_j, k, pool, stats,
                radius_leaves=radius_leaves, chunk=chunk, io=io,
                mindist_fn=part_mindist, scan_mode=scan_mode,
                label=label)
            sp.set(leaves_scanned=stats.leaves_scanned - b_scanned,
                   leaves_pruned=stats.leaves_pruned - b_pruned,
                   scan_bytes=stats.scan_bytes - b_bytes,
                   candidates=stats.candidates - b_cand)
        if stats.partitions_pruned == pruned_before:
            stats.partitions_touched += 1
    stats.add_timing("scan", (time.perf_counter() - t_scan) * 1e3)
    stats.pruned_frac = 1.0 - live_pairs / max(nq * total_rows, 1)
    best_d, best_off = pool.result()
    record_search(stats)
    return best_d, best_off, stats


def exact_knn(partitions: Sequence[Partition], queries,
              cfg: S.SummaryConfig, *, k: int = 1,
              ts_min: Optional[int] = None, temporal_prune: bool = True,
              bsf: Optional[np.ndarray] = None, radius_leaves: int = 1,
              chunk: int = 4096, io=None, mindist_fn=None,
              scan_mode: Optional[str] = None
              ) -> Tuple[np.ndarray, np.ndarray, SearchStats]:
    """Plan + execute in one call — the pipeline every exact-search entry
    point (tree, snapshot, sharded shard, mmap segment) delegates to."""
    queries_np = np.atleast_2d(np.asarray(queries, np.float32))
    stats = SearchStats()
    t0 = time.perf_counter()
    q_paas = query_paas(queries_np, cfg.segments, stats)
    plan = build_plan(partitions, q_paas, ts_min=ts_min,
                      temporal_prune=temporal_prune, io=io, stats=stats)
    plan_ms = (time.perf_counter() - t0) * 1e3
    d, off, stats = execute(plan, queries_np, k=k, bsf=bsf,
                            radius_leaves=radius_leaves, chunk=chunk,
                            io=io, mindist_fn=mindist_fn,
                            scan_mode=scan_mode, stats=stats)
    stats.add_timing("plan", plan_ms)
    return d, off, stats
