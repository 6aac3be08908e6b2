"""Coconut-Tree: bottom-up bulk-loaded, median-split, contiguous index.

Paper Sec. 4.3.  The index is a *sorted array* of (invSAX key, offset[, raw])
plus fence pointers — the static equivalent of a bulk-loaded UB-tree.  Because
the data is totally ordered by the z-order key:

* construction = summarize + sort (the external sort of Algorithm 3),
* every "leaf" (block of ``leaf_size`` consecutive entries) is 100% full
  except the last — median splitting taken to its limit,
* approximate search = binary search + a radius of adjacent leaves
  (Algorithm 4),
* exact search = SIMS (Algorithm 5): scan the in-memory summarizations with
  the mindist lower bound, fetch only unpruned raw series.

Materialized (``Coconut-Tree-Full``) stores raw series co-sorted with keys;
non-materialized stores offsets into the caller's raw array (gathers at query
time — the paper's extra I/O to the raw file, which our benchmarks surface as
gather cost).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import keys as K
from . import summarization as S
from .metrics import IOStats

__all__ = ["CoconutTree", "build", "approx_search", "exact_search",
           "approx_search_batch", "exact_search_batch",
           "exact_search_budgeted", "merge_trees", "SearchStats",
           "save", "load"]


@jax.jit
def take_rows(x: jax.Array, idx: jax.Array) -> jax.Array:
    """``x[idx]`` as one program.  Eager indexing compiles its index
    arithmetic (wrap-around of negative indices) as programs of their
    own, for every shape a scan gathers."""
    return x[idx]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CoconutTree:
    """Sorted, contiguous Coconut-Tree index (arrays live on device)."""
    keys: jax.Array                 # [N, n_words] uint32, z-order sorted
    codes: jax.Array                # [N, w] uint8 SAX words (sorted order)
    paas: jax.Array                 # [N, w] float32 PAA (sorted order)
    offsets: jax.Array              # [N] int32: position in original raw file
    raw: Optional[jax.Array]        # [N, L] sorted raw series (materialized)
    raw_ref: Optional[jax.Array]    # [N, L] *unsorted* raw (non-materialized)
    timestamps: Optional[jax.Array]  # [N] int32 insertion times (optional)
    ids: Optional[jax.Array] = None  # [N] int global row ids (sorted order)
    cfg: S.SummaryConfig = dataclasses.field(
        default_factory=S.SummaryConfig)
    leaf_size: int = 256

    # -- pytree plumbing ----------------------------------------------------
    def tree_flatten(self):
        children = (self.keys, self.codes, self.paas, self.offsets,
                    self.raw, self.raw_ref, self.timestamps, self.ids)
        aux = (self.cfg, self.leaf_size)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        cfg, leaf_size = aux
        return cls(*children, cfg=cfg, leaf_size=leaf_size)

    # -- conveniences --------------------------------------------------------
    @property
    def n(self) -> int:
        return int(self.keys.shape[0])

    @property
    def n_leaves(self) -> int:
        return -(-self.n // self.leaf_size)

    @property
    def materialized(self) -> bool:
        return self.raw is not None

    def series(self, idx: jax.Array) -> jax.Array:
        """Fetch raw series rows for sorted-order indices ``idx``."""
        if self.raw is not None:
            return take_rows(self.raw, idx)
        return take_rows(self.raw_ref, take_rows(self.offsets, idx))

    @property
    def fences(self) -> jax.Array:
        """First key of every leaf — the (implicit) internal-node layer."""
        return self.keys[:: self.leaf_size]


# SearchStats lives with the merger (the pipeline piece that owns query
# accounting); re-exported here because every search entry point returns
# one and historical callers import it as ``repro.core.tree.SearchStats``.
from ..query.merger import SearchStats  # noqa: E402


def _report_column(tree: CoconutTree):
    """Column reported as the 'offset' of an answer: the global row id
    when the tree carries ids (LSM runs), else the position in the
    original raw file (standalone trees keep their historical contract)."""
    return tree.ids if tree.ids is not None else tree.offsets


# A sort is among the slowest programs to compile on the TPU (a minute
# or more at run sizes), and eager code compiles every gather and index
# op of a flush or merge anew for each run size.  So: the build sorts at
# a power-of-two row count, which run sizes share; merges of sorted runs
# never sort; and each step's gathers are one program per shape.

@jax.jit
def _lexsort_padded(keys: jax.Array) -> jax.Array:
    return K.lexsort_keys(keys)


def _key_order(keys: jax.Array) -> jax.Array:
    """Stable lexicographic order of ``keys``, sorted with all-ones
    sentinel rows appended up to the next power of two: the sort is
    stable, so every sentinel lands after every real row and the first
    ``n`` entries are the order of ``keys`` alone."""
    n = keys.shape[0]
    pad = (1 << max(0, (n - 1).bit_length())) - n
    if pad:
        keys = jnp.pad(keys, ((0, pad), (0, 0)),
                       constant_values=np.uint32(0xFFFFFFFF))
    return _lexsort_padded(keys)[:n]


@jax.jit
def _gather_cols(order: jax.Array, keys: jax.Array, cols: dict):
    """``keys`` and every column of ``cols`` gathered by ``order``."""
    return keys[order], {name: c[order] for name, c in cols.items()}


@jax.jit
def _merge_cols(a_keys: jax.Array, b_keys: jax.Array, a_offs: jax.Array,
                b_offs: jax.Array, a_cols: dict, b_cols: dict):
    """Merge two sorted runs' keys, offsets and columns into one sorted
    run by rank, with no sort: row ``i`` of ``a`` goes after the rows of
    ``b`` with smaller keys, row ``j`` of ``b`` after the rows of ``a``
    with keys not larger — the stable sort of ``a`` then ``b``.  The
    offsets of the merged view address a virtual concatenated raw file,
    so ``b``'s are shifted past ``a``'s rows."""
    na, nb = a_keys.shape[0], b_keys.shape[0]
    ia = jnp.arange(na, dtype=jnp.int32)
    ib = jnp.arange(nb, dtype=jnp.int32)
    pos_a = ia + K.searchsorted_keys(b_keys, a_keys, side="left")
    pos_b = ib + K.searchsorted_keys(a_keys, b_keys, side="right")
    order = (jnp.zeros(na + nb, jnp.int32).at[pos_a].set(ia)
             .at[pos_b].set(na + ib))
    cols = {name: jnp.concatenate([a_cols[name], b_cols[name]])
            for name in a_cols}
    cols["offs"] = jnp.concatenate([a_offs, b_offs + na])
    keys, cols = _gather_cols(order, jnp.concatenate([a_keys, b_keys]), cols)
    return order, keys, cols


def build(raw: jax.Array,
          cfg: S.SummaryConfig,
          *,
          leaf_size: int = 256,
          materialized: bool = True,
          timestamps: Optional[jax.Array] = None,
          ids: Optional[jax.Array] = None,
          io: Optional[IOStats] = None,
          znorm: bool = False,
          paas: Optional[jax.Array] = None,
          codes: Optional[jax.Array] = None) -> CoconutTree:
    """Bulk-load a Coconut-Tree from raw series ``[N, L]`` (Algorithm 3).

    summarize -> invert (z-order) -> sort -> (optionally) co-sort raw.
    O(N/B) block transfers in the paper's model: we stream the raw file once
    (seq read), write the sorted summaries once (seq write), and for the
    materialized variant also rewrite the raw data once.

    ``paas``/``codes``: optional precomputed summaries in row order (both
    or neither) — the sharded router summarizes every batch once for
    routing and threads the result here so flushes never re-summarize.
    Must be the output of :func:`repro.core.summarization.summarize` on
    the same rows (row-wise, so slicing/concatenating batches is safe).
    """
    raw = jnp.asarray(raw, jnp.float32)
    if znorm:
        raw = S.znormalize(raw)
    n = raw.shape[0]
    if paas is None or codes is None:
        paas, codes = S.summarize(raw, cfg)
    else:
        paas = jnp.asarray(paas, jnp.float32)
        codes = jnp.asarray(codes, jnp.uint8)
    keys = S.invsax_keys(codes, cfg)
    cols = {"codes": codes, "paas": paas}
    if materialized:
        cols["raw"] = raw
    # device ids inherit the default int width (x64 is disabled); the
    # int64 view lives host-side (np conversions, segment files, WAL)
    if ids is not None:
        cols["ids"] = jnp.asarray(ids)
    if isinstance(timestamps, jax.Array):
        cols["ts"] = timestamps
    # the gather copies every column, the raw rows too: wait for the
    # summaries and the sort first, so their buffers are freed before it
    # claims its memory (queued behind them it ran a chip holding a 4 GiB
    # collection out of device memory)
    order = jax.block_until_ready(_key_order(keys))
    keys, cols = _gather_cols(order, keys, cols)
    ts = cols.get("ts")
    if timestamps is not None and ts is None:     # host timestamps stay so
        ts = timestamps[np.asarray(order)]
    if io is not None:
        io.seq_read(n)            # pass over the raw file (summarize)
        io.seq_write(n)           # write sorted summaries
        io.seq_read(n)            # merge pass read
        io.seq_write(n)           # merge pass write
        if materialized:
            io.seq_read(n)        # extra pass: co-sort raw into leaves
            io.seq_write(n)
    return CoconutTree(
        keys=keys, codes=cols["codes"], paas=cols["paas"],
        offsets=order.astype(jnp.int32), raw=cols.get("raw"),
        raw_ref=None if materialized else raw,
        timestamps=ts, ids=cols.get("ids"), cfg=cfg, leaf_size=leaf_size)


# ---------------------------------------------------------------------------
# Approximate search (Algorithm 4)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("radius_leaves",))
def _approx_candidates(tree: CoconutTree, query: jax.Array,
                       radius_leaves: int = 1):
    """Return (cand_dists_sq, cand_sorted_idx) for the leaves around the
    query's z-order insertion point.  Fixed-size => jit-friendly."""
    cfg = tree.cfg
    q = query.astype(jnp.float32)
    q_paa = S.paa(q[None, :], cfg.segments)[0]
    q_codes = S.sax_encode(q_paa[None, :], cfg.bits)
    q_key = K.interleave_codes(q_codes, w=cfg.segments, b=cfg.bits)
    pos = K.searchsorted_keys(tree.keys, q_key)[0]
    span = 2 * radius_leaves * tree.leaf_size
    start = jnp.clip(pos - span // 2, 0, jnp.maximum(tree.n - span, 0))
    idx = start + jnp.arange(span, dtype=jnp.int32)
    idx = jnp.clip(idx, 0, tree.n - 1)
    cand = tree.series(idx)
    d = S.euclidean_sq(q, cand)
    return d, idx


def approx_search(tree: CoconutTree, query: jax.Array, *,
                  k: int = 1,
                  radius_leaves: int = 1,
                  io: Optional[IOStats] = None
                  ) -> Tuple[np.ndarray, np.ndarray, SearchStats]:
    """Approximate k-NN: visit the leaves around the query's sorted position.

    Thin wrapper over :func:`approx_search_batch` with Q=1: returns
    (dists ``[k]``, offsets ``[k]``, stats).  The pre-PR-4 scalar return
    (``float``, ``int``) is gone — index ``[0]`` for the old contract.
    """
    q = jnp.asarray(query, jnp.float32)[None, :]
    d, off, stats = approx_search_batch(
        tree, q, k=k, radius_leaves=radius_leaves, io=io)
    return d[0], off[0], stats


# ---------------------------------------------------------------------------
# Exact search: SIMS (Algorithm 5)
# ---------------------------------------------------------------------------

def exact_search(tree: CoconutTree, query: jax.Array, *,
                 k: int = 1,
                 radius_leaves: int = 1,
                 chunk: int = 4096,
                 io: Optional[IOStats] = None,
                 mindist_fn=None,
                 ts_min: Optional[int] = None,
                 bsf: Optional[float] = None,
                 budget=None,
                 mode: str = "exact",
                 ) -> Tuple[np.ndarray, np.ndarray, SearchStats]:
    """Exact k-NN via the skip-sequential SIMS scan.

    Thin wrapper over :func:`exact_search_batch` with Q=1 — one pipeline
    serves the single and batched paths, so the answer bits are
    identical by construction.  Returns (dists ``[k]``, offsets ``[k]``,
    stats); the pre-PR-4 scalar return is gone — index ``[0]``.

    ``ts_min``: if set, restrict to entries with timestamp >= ts_min
    (post-processing window filtering, Sec. 5.1).
    ``bsf``: externally-known bound (LSM run / shard chaining); it prunes
    the scan but is never returned as an answer — a caller chaining
    components keeps its own best and compares.
    ``mindist_fn``: injectable kernel with the BATCHED signature
    ``(q_paas [Q, w], codes [N, w]) -> [Q, N]``.
    ``budget`` / ``mode``: the recall/latency dial — see
    :func:`exact_search_batch`.
    """
    q = jnp.asarray(query, jnp.float32)[None, :]
    ext = None if bsf is None else np.asarray([bsf], np.float32)
    d, off, stats = exact_search_batch(
        tree, q, k=k, radius_leaves=radius_leaves,
        chunk=chunk, io=io, mindist_fn=mindist_fn, ts_min=ts_min, bsf=ext,
        budget=budget, mode=mode)
    return d[0], off[0], stats


@functools.partial(jax.jit, static_argnames=("budget", "radius_leaves"))
def exact_search_budgeted(tree: CoconutTree, query: jax.Array, *,
                          budget: int = 1024, radius_leaves: int = 1):
    """Jit-friendly exact search with a fixed verification budget.

    Verifies the ``budget`` smallest-mindist candidates.  Returns
    (best_d, best_offset, certified) where ``certified`` is True iff the
    (budget)-th smallest mindist already exceeds the best found distance —
    i.e. the answer is provably exact.  Used on the serving path where
    data-dependent shapes are not allowed.
    """
    q = jnp.asarray(query, jnp.float32)
    d0, idx = _approx_candidates(tree, q, radius_leaves=radius_leaves)
    seed = jnp.min(d0)
    cfg = tree.cfg
    q_paa = S.paa(q[None, :], cfg.segments)[0]
    md = S.mindist_sq(q_paa, tree.codes, cfg)
    neg_md, order = jax.lax.top_k(-md, budget)
    cand_md = -neg_md
    rows = tree.series(order)
    d = S.euclidean_sq(q, rows)
    d = jnp.where(cand_md < jnp.minimum(seed, d.min()), d, jnp.inf)
    best_i = jnp.argmin(d)
    best_d = jnp.minimum(d[best_i], seed)
    from_seed = seed <= d[best_i]
    rep = _report_column(tree)
    seed_off = rep[idx[jnp.argmin(d0)]]
    best_off = jnp.where(from_seed, seed_off, rep[order[best_i]])
    certified = cand_md[budget - 1] >= best_d
    return best_d, best_off, certified


# ---------------------------------------------------------------------------
# Batched multi-query search: one summarization pass serves a whole batch
# ---------------------------------------------------------------------------

# pool merging lives with the merger; re-imported for the approx path
from ..query.merger import merge_topk as _merge_topk  # noqa: E402


@functools.partial(jax.jit, static_argnames=("radius_leaves",))
def _approx_candidates_batch(tree: CoconutTree, queries: jax.Array,
                             radius_leaves: int = 1):
    """Vectorized Algorithm 4 probe: one binary-search + gather for the
    whole batch.  queries ``[Q, L]`` -> (dists ``[Q, span]``, idx ``[Q, span]``)."""
    cfg = tree.cfg
    q = queries.astype(jnp.float32)
    q_paa = S.paa(q, cfg.segments)                       # [Q, w]
    q_codes = S.sax_encode(q_paa, cfg.bits)
    q_keys = K.interleave_codes(q_codes, w=cfg.segments, b=cfg.bits)
    pos = K.searchsorted_keys(tree.keys, q_keys)         # [Q]
    span = 2 * radius_leaves * tree.leaf_size
    start = jnp.clip(pos - span // 2, 0, jnp.maximum(tree.n - span, 0))
    idx = start[:, None] + jnp.arange(span, dtype=jnp.int32)[None, :]
    idx = jnp.clip(idx, 0, tree.n - 1)                   # [Q, span]
    cand = tree.series(idx)                              # [Q, span, L]
    d = S.sum_sq(cand - q[:, None, :])
    return d, idx


def approx_search_batch(tree: CoconutTree, queries: jax.Array, *,
                        k: int = 1, radius_leaves: int = 1,
                        io: Optional[IOStats] = None
                        ) -> Tuple[np.ndarray, np.ndarray, SearchStats]:
    """Batched approximate k-NN (generalizes :func:`approx_search` to Q
    queries and top-k answers).

    Returns (dists ``[Q, k]``, offsets ``[Q, k]``, stats); ``offsets`` index
    the original raw file, padded with -1 (dist inf) when fewer than k
    candidates exist.  Row ``[qi, 0]`` with k=1 equals
    ``approx_search(tree, queries[qi])``.
    """
    queries = jnp.atleast_2d(jnp.asarray(queries, jnp.float32))
    nq = queries.shape[0]
    d, idx = _approx_candidates_batch(tree, queries,
                                      radius_leaves=radius_leaves)
    d = np.asarray(d)
    offs = np.asarray(_report_column(tree))[np.asarray(idx)]   # [Q, span]
    out_d = np.empty((nq, k), np.float32)
    out_o = np.empty((nq, k), np.int64)
    for qi in range(nq):
        out_d[qi], out_o[qi] = _merge_topk(d[qi], offs[qi], k)
    stats = SearchStats(candidates=len(np.unique(idx)),
                        leaves_touched=2 * radius_leaves,
                        exact=False, queries=nq)
    stats.candidates_per_query = np.full(nq, d.shape[1], np.int64)
    stats.leaves_per_query = np.full(nq, 2 * radius_leaves, np.int64)
    if io is not None:
        io.rand_read(2 * radius_leaves * nq)
    return out_d, out_o, stats


def exact_search_batch(tree: CoconutTree, queries: jax.Array, *,
                       k: int = 1, radius_leaves: int = 1,
                       chunk: int = 4096,
                       io: Optional[IOStats] = None,
                       mindist_fn=None,
                       ts_min: Optional[int] = None,
                       bsf: Optional[np.ndarray] = None,
                       budget=None,
                       mode: str = "exact",
                       ) -> Tuple[np.ndarray, np.ndarray, SearchStats]:
    """Batched exact k-NN via ONE amortized SIMS scan (the tentpole path).

    Delegates to the unified query pipeline
    (:mod:`repro.query`): the partition's leaf fences price every leaf
    with a z-order envelope mindist bound, the executor scans only the
    surviving leaves cheapest-bound-first (skip-sequential SIMS),
    verifies unpruned rows with the batched Euclidean kernel, and the
    merger chains the per-query k-th-best bound across chunks.

    ``bsf``: optional ``[Q]`` per-query external bounds (LSM run chaining).
    ``mindist_fn``: injectable lower-bound kernel,
    ``(q_paas [Q, w], codes [B, w]) -> [Q, B]`` (defaults to
    :func:`repro.core.summarization.mindist_sq_batch`; the Pallas kernel
    drops in via ``repro.kernels.ops.mindist_batch``).
    ``budget`` / ``mode="approx"``: the recall/latency dial — drain the
    best-first leaf frontier under a :class:`repro.query.Budget` (an int
    is ``max_leaves`` shorthand) and report the certified lower-bound
    gap in ``stats.gap``; passing ``budget`` implies approx mode, and
    ``mode="approx"`` with no budget is bit-identical to exact with
    ``gap == 0``.
    Returns (dists ``[Q, k]``, offsets ``[Q, k]``, batch stats); with k=1
    row qi matches ``exact_search(tree, queries[qi])``.
    """
    from ..query import Partition, approx_knn, exact_knn
    queries = np.atleast_2d(np.asarray(queries, np.float32))
    if mode not in ("exact", "approx"):
        raise ValueError(f"mode must be 'exact' or 'approx', got {mode!r}")
    if budget is not None or mode == "approx":
        return approx_knn([Partition.from_tree(tree)], queries, tree.cfg,
                          k=k, budget=budget, ts_min=ts_min, bsf=bsf,
                          radius_leaves=radius_leaves, chunk=chunk,
                          io=io, mindist_fn=mindist_fn)
    return exact_knn([Partition.from_tree(tree)], queries, tree.cfg,
                     k=k, ts_min=ts_min, bsf=bsf,
                     radius_leaves=radius_leaves, chunk=chunk, io=io,
                     mindist_fn=mindist_fn)


# ---------------------------------------------------------------------------
# Merging (LSM compaction building block)
# ---------------------------------------------------------------------------

def merge_trees(a: CoconutTree, b: CoconutTree, *,
                io: Optional[IOStats] = None) -> CoconutTree:
    """Sort-merge two Coconut-Trees into one (LSM compaction, Sec. 4.4).

    On device this is concat + lexsort; in the paper's I/O model it is a
    sequential read of both runs and a sequential write of the result.
    """
    if a.cfg != b.cfg:
        raise ValueError("cannot merge trees with different summary configs")
    if a.materialized != b.materialized:
        raise ValueError("cannot merge materialized with non-materialized")
    a_cols = {"codes": a.codes, "paas": a.paas}
    b_cols = {"codes": b.codes, "paas": b.paas}
    if a.timestamps is not None and b.timestamps is not None:
        a_cols["ts"], b_cols["ts"] = a.timestamps, b.timestamps
    if a.ids is not None and b.ids is not None:
        a_cols["ids"], b_cols["ids"] = a.ids, b.ids
    raw_ref = None
    if a.materialized:
        a_cols["raw"], b_cols["raw"] = a.raw, b.raw
    else:
        raw_ref = jnp.concatenate([a.raw_ref, b.raw_ref])
    _order, keys, cols = _merge_cols(a.keys, b.keys, a.offsets, b.offsets,
                                     a_cols, b_cols)
    if io is not None:
        io.seq_read(a.n + b.n)
        io.seq_write(a.n + b.n)
    return CoconutTree(
        keys=keys, codes=cols["codes"], paas=cols["paas"],
        offsets=cols["offs"].astype(jnp.int32), raw=cols.get("raw"),
        raw_ref=raw_ref, timestamps=cols.get("ts"), ids=cols.get("ids"),
        cfg=a.cfg, leaf_size=a.leaf_size)


# ---------------------------------------------------------------------------
# Persistence (delegates to the storage engine; lazy import keeps core
# importable without touching disk-facing code)
# ---------------------------------------------------------------------------

def save(tree: CoconutTree, path: str, *,
         io: Optional[IOStats] = None) -> None:
    """Persist the tree as one self-describing on-disk segment file."""
    from ..storage.segment import write_segment
    write_segment(path, tree, io=io)


def load(path: str) -> CoconutTree:
    """Reopen a segment file written by :func:`save` as a ``CoconutTree``.

    The columns are already sorted on disk, so searches on the loaded tree
    are identical to the tree that was saved.
    """
    from ..storage.segment import Segment
    seg = Segment.open(path)
    try:
        return seg.to_tree()
    finally:
        seg.close()
