"""The uniform partition view every search source exposes to the planner.

A :class:`Partition` is one searchable unit — a sorted Coconut run held
on device (:class:`repro.core.tree.CoconutTree`), a sorted run on disk
(:class:`repro.storage.segment.Segment`, read zero-copy through its
mmap), or an unsorted frozen insert buffer
(:class:`repro.ingest.snapshot.FrozenBuffer`) — normalized to the five
things the pipeline needs: ``(keys, codes, leaf_fences, ts_range,
backend)``.

Sorted partitions additionally answer *leaf-granular* questions: the
leaf-first z-order keys (fence pointers) from which the planner derives
per-leaf mindist bounds, and row-subset accessors (``codes_rows`` /
``series_rows``) that gather only the surviving leaves — on device for
trees, as real ``bytes_read``-charged mmap reads for segments.  The
unsorted buffer has no fences and is brute-force scanned by the
executor.

Segment partitions optionally carry a
:class:`repro.storage.tiers.TieredLeafStore`: row gathers then assemble
from leaf-granular cached blocks (host-RAM warm tier, device-promoted
hot tier) and fall through to the mmap only on a miss — a caching
backend is just another Partition view, so the planner/executor above
this seam is unchanged and answers are bit-identical across tiers.
Byte accounting keeps two strict currencies: a miss charges the
*stored* (packed) bytes to ``io.bytes_read``; a hit charges nothing to
``io`` and credits the same figure to ``cache.bytes_saved``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from ..core import summarization as S
from ..core.metrics import IOStats
from .merger import SearchStats, to_host

__all__ = ["Partition"]


@dataclasses.dataclass
class Partition:
    """One searchable unit behind the planner/executor pipeline."""
    kind: str                 # "tree" | "segment" | "buffer"
    backend: str              # "device" | "mmap" | "host"
    cfg: S.SummaryConfig
    n: int
    leaf_size: int
    source: object
    ts_range: Optional[Tuple[int, int]] = None   # (t_min, t_max) or None
    tiers: Optional[object] = None               # TieredLeafStore or None

    # ------------------------------------------------------------ constructors
    @classmethod
    def from_tree(cls, tree, *, ts_range: Optional[Tuple[int, int]] = None
                  ) -> "Partition":
        """Wrap an in-memory/device ``CoconutTree`` (or one LSM run's)."""
        return cls(kind="tree", backend="device", cfg=tree.cfg,
                   n=tree.n, leaf_size=tree.leaf_size, source=tree,
                   ts_range=ts_range)

    @classmethod
    def from_run(cls, run) -> "Partition":
        """Wrap one LSM :class:`~repro.core.lsm.Run` (tree + time range)."""
        return cls.from_tree(run.tree, ts_range=(run.t_min, run.t_max))

    @classmethod
    def from_segment(cls, seg, *,
                     ts_range: Optional[Tuple[int, int]] = None,
                     tiers: Optional[object] = None) -> "Partition":
        """Wrap an on-disk :class:`~repro.storage.segment.Segment`; all
        row access goes through the mmap and is charged to ``io``.
        ``ts_range`` is optional — computing it would read the whole
        timestamp column, so callers that know it (the LSM manifest
        records t_min/t_max per run) pass it in.  ``tiers`` attaches a
        :class:`~repro.storage.tiers.TieredLeafStore` so leaf blocks are
        served from cache when warm."""
        return cls(kind="segment", backend="mmap", cfg=seg.cfg,
                   n=seg.n, leaf_size=seg.leaf_size, source=seg,
                   ts_range=ts_range, tiers=tiers)

    @classmethod
    def from_buffer(cls, buf, cfg: S.SummaryConfig, *,
                    ts_range: Optional[Tuple[int, int]] = None
                    ) -> "Partition":
        """Wrap a frozen (unsorted) insert buffer — brute-force scanned."""
        return cls(kind="buffer", backend="host", cfg=cfg,
                   n=buf.n, leaf_size=max(1, buf.n), source=buf,
                   ts_range=ts_range)

    # -------------------------------------------------------------- properties
    @property
    def is_sorted(self) -> bool:
        return self.kind != "buffer"

    @property
    def n_leaves(self) -> int:
        return -(-self.n // self.leaf_size)

    @property
    def cache_token(self):
        """Cache group key for this partition's leaf blocks: the segment
        path.  Segment files are immutable once published and their ids
        are never reused, so the path identifies the bytes forever."""
        return getattr(self.source, "path", None)

    @property
    def is_packed(self) -> bool:
        """True when the source stores bit-packed v3 code rows — the
        executor's cue that the fused unpack+mindist path applies."""
        return (self.kind == "segment"
                and getattr(self.source, "codes_packed", None) is not None)

    @property
    def code_row_bytes(self) -> int:
        """Stored bytes per code row — what one row costs to read."""
        if self.kind == "segment":
            return self.source.code_row_bytes
        return self.cfg.segments

    # ----------------------------------------------------------- sorted access
    def leaf_fences(self, io: Optional[IOStats] = None,
                    stats: Optional[SearchStats] = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """(leaf-first keys ``[n_leaves, n_words]`` uint32, last key
        ``[n_words]``) — the implicit internal-node layer the planner
        turns into per-leaf code envelopes.  ``stats`` counts a tree's
        device reads."""
        if self.kind == "tree":
            fences = to_host(self.source.fences, stats)
            last = to_host(self.source.keys[-1:], stats)[0]
        else:
            fences = np.asarray(self.source.fences)
            last = np.asarray(self.source.keys[self.n - 1])
            if io is not None:
                io.read_bytes(fences.nbytes + last.nbytes)
        return fences, last

    def seed_window(self, queries, *, radius_leaves: int = 1,
                    io: Optional[IOStats] = None,
                    q_paas=None,
                    stats: Optional[SearchStats] = None) -> np.ndarray:
        """Row indices ``[Q, span]`` of the rows around each query's
        z-order insertion point (the Algorithm-4 probe that seeds the
        exact scan's best-so-far pool).

        Both backends resolve the *row-granular* insertion point — the
        tree by binary search over its device key column, the segment by
        a fence search refined inside ONE leaf of the mmap'd key column
        — so the probe windows (and hence budgeted answers) are
        identical across backends.  ``q_paas``: optional precomputed
        query PAA (the plan already holds it) — avoids a second
        summarization on the segment path.  ``stats`` counts the tree's
        device read of the window."""
        import jax.numpy as jnp
        if self.kind == "tree":
            from ..core.tree import _approx_candidates_batch
            _, idx = _approx_candidates_batch(
                self.source, jnp.asarray(queries),
                radius_leaves=radius_leaves)
            idx = to_host(idx, stats)
        else:
            from ..core import keys as K
            seg = self.source
            cfg = self.cfg
            queries = np.atleast_2d(np.asarray(queries, np.float32))
            nq = queries.shape[0]
            if q_paas is None:
                q_paas = S.paa(jnp.asarray(queries), cfg.segments)
            q_codes = S.sax_encode(jnp.asarray(q_paas), cfg.bits)
            q_keys = np.asarray(K.interleave_codes(
                q_codes, w=cfg.segments, b=cfg.bits))
            # fence bytes were already charged when the planner read the
            # fence column for the leaf envelopes; the probe rereads the
            # same (now hot) pages, so it is not charged again
            fences = np.asarray(seg.fences)
            if len(fences):
                fl = np.asarray(K.searchsorted_keys(jnp.asarray(fences),
                                                    jnp.asarray(q_keys)))
            else:
                fl = np.zeros(nq, np.int32)
            # refine to the global row insertion point: it lies in the
            # leaf just before the first fence >= q_key (everything
            # earlier is strictly below the query key), so one leaf of
            # the key column per query resolves it exactly
            pos = np.zeros(nq, np.int64)
            for qi in range(nq):
                if int(fl[qi]) == 0:
                    continue                   # keys[0] >= q_key: pos 0
                l = int(fl[qi]) - 1
                s = l * self.leaf_size
                blk = np.asarray(self._leaf_block("keys", l, io))
                lt = np.zeros(len(blk), bool)
                und = np.ones(len(blk), bool)
                for w in range(blk.shape[1]):  # lexicographic <
                    bw = blk[:, w]
                    qw = q_keys[qi, w]
                    lt |= und & (bw < qw)
                    und &= bw == qw
                pos[qi] = s + int(np.count_nonzero(lt))
            span = 2 * radius_leaves * self.leaf_size
            start = np.clip(pos - span // 2, 0, max(self.n - span, 0))
            idx = start[:, None] + np.arange(span)[None, :]
            idx = np.clip(idx, 0, self.n - 1)
        if io is not None:
            io.rand_read(2 * radius_leaves * len(idx))
        return idx

    # ------------------------------------------------------------- leaf tiers
    def _leaf_block(self, col: str, li: int,
                    io: Optional[IOStats] = None):
        """One leaf of the ``codes`` (stored form: packed on v3) or
        ``keys`` (decoded) column, through the tier cache when attached.

        A hit returns the cached block (possibly device-resident for hot
        code leaves) with no ``io`` charge — the tier store credits the
        stored bytes to ``cache.bytes_saved`` instead.  A miss reads the
        mmap, charges the stored bytes to ``io.bytes_read``, and admits
        the block to the warm tier.
        """
        seg = self.source
        s = li * self.leaf_size
        e = min(s + self.leaf_size, self.n)
        if col == "codes":
            stored = (e - s) * self.code_row_bytes
        else:
            stored = seg.keys_leaf_nbytes(li)
        if self.tiers is not None:
            blk = self.tiers.get(self.cache_token, col, li, stored)
            if blk is not None:
                return blk
        if col == "codes":
            src = seg.codes_packed
            blk = np.asarray((seg.codes if src is None else src)[s:e])
        else:
            blk = np.asarray(seg.keys[s:e])
        if io is not None:
            io.read_bytes(stored)
            if col == "codes":
                io.seq_read(e - s)
        if self.tiers is not None:
            self.tiers.admit(self.cache_token, col, li, blk, stored)
        return blk

    def _gather_rows(self, col: str, idx: np.ndarray,
                     io: Optional[IOStats] = None):
        """Stored-form rows for sorted indices, assembled leaf-by-leaf
        through the cache.  Stays on device when every touched block is
        device-resident (the hot tier feeding the fused kernel with no
        host→device copy)."""
        idx = np.asarray(idx)
        leaves = idx // self.leaf_size
        parts, device = [], True
        for li in np.unique(leaves):           # sorted, like idx
            blk = self._leaf_block(col, int(li), io)
            local = idx[leaves == li] - int(li) * self.leaf_size
            if isinstance(blk, np.ndarray):
                device = False
                parts.append(blk[local])
            else:
                from ..core.tree import take_rows
                parts.append(take_rows(blk, local))
        if len(parts) == 1:
            return parts[0]
        if device:
            import jax.numpy as jnp
            return jnp.concatenate(parts)
        return np.concatenate([np.asarray(p) for p in parts])

    def codes_rows(self, idx: np.ndarray,
                   io: Optional[IOStats] = None):
        """Full-width SAX code rows for sorted-order indices (device
        array for trees, cache/mmap reads charged at stored width for
        segments)."""
        if self.kind == "tree":
            import jax.numpy as jnp
            from ..core.tree import take_rows
            return take_rows(self.source.codes, jnp.asarray(idx))
        if self.kind == "segment" and self.tiers is not None:
            blk = self._gather_rows("codes", idx, io)
            if self.is_packed:
                from ..storage.packing import unpack_codes
                return unpack_codes(np.asarray(blk), self.cfg.segments,
                                    self.cfg.bits)
            return np.asarray(blk)
        blk = np.asarray(self.source.codes[idx])
        if io is not None:
            io.read_bytes(len(blk) * self.code_row_bytes)
            io.seq_read(len(blk))
        return blk

    def codes_rows_packed(self, idx: np.ndarray,
                          io: Optional[IOStats] = None):
        """Packed (stored-form) code rows — the fused unpack+mindist
        kernel's input.  Only meaningful when :attr:`is_packed`."""
        if self.tiers is not None:
            return self._gather_rows("codes", idx, io)
        blk = np.asarray(self.source.codes_packed[idx])
        if io is not None:
            io.read_bytes(blk.nbytes)
            io.seq_read(len(blk))
        return blk

    def series_rows(self, idx: np.ndarray,
                    io: Optional[IOStats] = None):
        """Raw rows for sorted-order indices (verification fetch)."""
        if self.kind == "tree":
            import jax.numpy as jnp
            return self.source.series(jnp.asarray(idx))
        if self.kind == "segment":
            return self.source.series_rows(idx, io=io)
        return self.source.raw[idx]

    # ------------------------------------------------------------- row columns
    def report_ids(self) -> np.ndarray:
        """Column reported as the 'offset' of an answer: the global row
        id when the partition carries ids (LSM runs), else the position
        in the original raw file (standalone trees/segments keep their
        historical contract)."""
        src = self.source
        if self.kind == "buffer":
            return np.asarray(src.ids)
        col = src.ids if src.ids is not None else src.offsets
        return np.asarray(col)

    def timestamps(self) -> Optional[np.ndarray]:
        if self.kind == "buffer":
            return np.asarray(self.source.ts)
        ts = self.source.timestamps
        return None if ts is None else np.asarray(ts)

    def buffer_raw(self) -> np.ndarray:
        return np.asarray(self.source.raw)
