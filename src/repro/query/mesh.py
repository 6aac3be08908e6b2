"""MeshScanEngine: pinned device-sharded shard columns + one-launch scan.

The residency half of the device-resident sharded scan
(:mod:`repro.kernels.mesh_scan` is the compute half).  The engine owns:

* **Pinning** — stacking every shard's immutable run columns (SAX codes,
  raw series, global ids, timestamps) into ``[S, cap, ...]`` arrays
  padded to a bucket-rounded capacity, sharded ``PartitionSpec('shard',
  ...)`` over a 1-D scan mesh, so a probe batch launches with zero
  host->device column traffic.  Each device's block is built on that
  device from its shards' device columns (``jnp`` concatenation and
  padding, ids of -1 for padding) and the blocks are assembled into the
  stacks in place (``jax.make_array_from_single_device_arrays``): the
  sharded engine keeps shard ``i`` on mesh device ``i * D // S``
  (``launch.mesh.shard_device``), so no row moves and none passes
  through the host.  Columns that live elsewhere (an engine reopened on
  one device) are copied to their mesh device first.
* **Freshness** — a per-snapshot fingerprint ``(id(run.tree), rows,
  segment)`` per shard.  Runs are immutable once published, so any
  flush, merge, or rebalance yields a different run tuple and the next
  probe repins; the pinned state keeps strong references to the runs it
  mirrors, so an ``id()`` can never be recycled while it is part of a
  live fingerprint.  A probe therefore *cannot* read a stale device
  block: either the fingerprint matches (device state mirrors exactly
  the snapshot's runs) or the state is rebuilt from the snapshot.
* **Invalidation hooks** — :meth:`on_invalidate` subscribes to
  ``TieredLeafStore`` invalidation (segment GC after flush / merge /
  rebalance) and drops the pinned stacks eagerly.  This is a
  device-memory-hygiene fast path, not a correctness requirement — the
  fingerprint already forces the rebuild — so it is deliberately
  conservative: any invalidation clears everything.

What is NOT pinned: frozen insert buffers (unsorted, mutating every
insert) are scanned host-side by the caller first, and their k-th
distances seed the launch ``bound`` — the same bsf-chaining the
threaded fan-out applies across shards, applied across the whole mesh.

Answers: every distance the repo returns adds a row's squares in one
fixed pairwise order (``summarization.sum_sq``), the launch's bodies
(the jnp twin and the Pallas ``scan_verify``) included, so a row's
distance has the same bits whichever path verified it.  The launch's
distances and ids are therefore the answer as they come off the
device; its three outputs are read through ``merger.to_host`` (one
``exec.sync`` span and one ``SearchStats.device_syncs`` count each).
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import summarization as S
from ..kernels import ops
from ..launch.mesh import SCAN_AXIS, make_scan_mesh
from ..obs import get_registry, span as _span
from .merger import SearchStats, to_host
from .planner import DeviceLayout, build_device_layout

__all__ = ["MeshScanEngine", "PinnedShards"]


@dataclasses.dataclass(frozen=True)
class PinnedShards:
    """One immutable pinned generation: the device columns of one exact
    run-set.  Strong ``runs`` refs keep every pinned tree alive so the
    fingerprint's ``id()`` components stay unambiguous."""
    fingerprint: tuple
    layout: DeviceLayout
    mesh: object
    codes: jax.Array               # [S, cap, w] uint8, sharded dim 0
    raw: jax.Array                 # [S, cap, L] float32
    ids: jax.Array                 # [S, cap] int32, -1 marks padding
    ts: jax.Array                  # [S, cap] int32 (zeros when absent)
    has_ts: bool                   # every pinned run carries timestamps
    rows: Tuple[int, ...]          # per-shard pinned row counts
    leaves: Tuple[int, ...]        # per-shard pinned leaf counts
    runs: tuple
    nbytes: int


@functools.partial(jax.jit, static_argnames=("cap", "fill"))
def _pinned_block(shards: tuple, *, cap: int, fill):
    """``[spd, cap, ...]``: each shard's run columns (a tuple per
    shard) concatenated and padded to ``cap`` rows with ``fill``, one
    program and one buffer on the device that holds them."""
    out = []
    for runs in shards:
        x = jnp.concatenate(runs) if len(runs) > 1 else runs[0]
        pad = [(0, cap - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
        out.append(jnp.pad(x, pad, constant_values=fill))
    return jnp.stack(out)


def _run_columns(tree):
    """(codes, raw, ids, ts) of a run as device arrays (ids and
    timestamps are int32 on the device); missing timestamps read 0."""
    from ..core.tree import take_rows
    raw = (tree.raw if tree.raw is not None
           else take_rows(tree.raw_ref, tree.offsets))
    ts = (jnp.zeros(tree.n, jnp.int32) if tree.timestamps is None
          else tree.timestamps)
    return tree.codes, raw, tree.ids, ts


class MeshScanEngine:
    """Thread-safe owner of the pinned device state for one sharded
    index.  ``pin`` returns the current generation (rebuilding if the
    snapshot moved), ``launch`` runs the compiled mesh pass against it.
    """

    def __init__(self, cfg: S.SummaryConfig, *, axis: str = SCAN_AXIS,
                 bucket: int = 2048,
                 max_pin_bytes: Optional[int] = None):
        self.cfg = cfg
        self.axis = axis
        self.bucket = int(bucket)
        self.max_pin_bytes = max_pin_bytes
        self._lock = threading.Lock()
        self._pinned: Optional[PinnedShards] = None
        self._reg = get_registry()
        # eager registration: operators see the full family at first
        # scrape, including the zero fallback count of a healthy server
        for c in ("query.mesh_launches_total",
                  "query.mesh_fallbacks_total",
                  "query.mesh_pins_total",
                  "query.mesh_invalidations_total"):
            self._reg.counter(c)

    # ------------------------------------------------------------ invalidation
    def on_invalidate(self, token=None) -> None:
        """``TieredLeafStore`` invalidation hook: a segment left the
        store, so the run set moved — drop every pinned stack now
        (frees device memory ahead of the fingerprint-forced repin)."""
        del token
        with self._lock:
            had = self._pinned is not None
            self._pinned = None
        if had:
            self._reg.counter("query.mesh_invalidations_total").inc()
            self._reg.gauge("query.mesh_pinned_bytes").set(0)

    def fallback(self, reason: str) -> None:
        """Record one probe batch taking the threaded seam instead."""
        self._reg.counter("query.mesh_fallbacks_total").inc()
        self._reg.counter(f"query.mesh_fallback.{reason}_total").inc()

    # ----------------------------------------------------------------- pinning
    @staticmethod
    def _fingerprint(snaps: Sequence) -> tuple:
        return tuple(tuple((id(r.tree), r.n, r.segment) for r in sn.runs)
                     for sn in snaps)

    def pin(self, snaps: Sequence) -> Optional[PinnedShards]:
        """The pinned generation of ``snaps`` (one Snapshot per shard),
        rebuilding if any shard's run set changed.  Returns None when
        the snapshot cannot be pinned (a run without ids, or the pin
        budget would be exceeded) — the caller must fall back to the
        threaded path."""
        fp = self._fingerprint(snaps)
        with self._lock:
            cur = self._pinned
            if cur is not None and cur.fingerprint == fp:
                return cur
            pinned = self._build(snaps, fp)
            if pinned is not None:
                self._pinned = pinned
                self._reg.counter("query.mesh_pins_total").inc()
                self._reg.gauge("query.mesh_pinned_bytes").set(
                    pinned.nbytes)
            return pinned

    def _build(self, snaps: Sequence,
               fp: tuple) -> Optional[PinnedShards]:
        w, L = self.cfg.segments, self.cfg.series_len
        with _span("mesh_pin", shards=len(snaps)):
            shards, runs, has_ts, leaves = [], [], True, []
            for sn in snaps:
                cols, lv = [], 0
                for r in sn.runs:
                    if r.tree.ids is None:
                        return None
                    has_ts = has_ts and r.tree.timestamps is not None
                    cols.append(_run_columns(r.tree))
                    lv += r.tree.n_leaves
                    runs.append(r)
                shards.append(cols)
                leaves.append(lv)
            row_counts = [sum(int(c[2].shape[0]) for c in cols)
                          for cols in shards]
            mesh = make_scan_mesh(len(snaps), axis=self.axis)
            layout = build_device_layout(
                row_counts, n_devices=mesh.devices.size,
                bucket=self.bucket)
            s, cap = layout.n_shards, layout.cap
            spd = layout.shards_per_device
            nbytes = s * cap * (w + 4 * L + 4 + 4)
            if self.max_pin_bytes is not None \
                    and nbytes > self.max_pin_bytes:
                return None
            empty = (np.zeros((0, w), np.uint8),
                     np.zeros((0, L), np.float32),
                     np.zeros(0, np.int32), np.zeros(0, np.int32))
            blocks = ([], [], [], [])
            for d, dev in enumerate(mesh.devices.flat):
                # the device's shards: each column of each run there, a
                # no-op put where the shard already lives on ``dev``
                mine = [[jax.device_put(c, dev) for c in cols]
                        if cols else [jax.device_put(empty, dev)]
                        for cols in shards[d * spd:(d + 1) * spd]]
                for col, fill in enumerate((0, 0.0, -1, 0)):
                    blocks[col].append(_pinned_block(
                        tuple(tuple(c[col] for c in runs_)
                              for runs_ in mine), cap=cap, fill=fill))

            def stack(col, tail):
                spec = NamedSharding(mesh, P(self.axis,
                                             *([None] * len(tail))))
                return jax.make_array_from_single_device_arrays(
                    (s, cap) + tail, spec, blocks[col])

            return PinnedShards(
                fingerprint=fp, layout=layout, mesh=mesh,
                codes=stack(0, (w,)), raw=stack(1, (L,)),
                ids=stack(2, ()), ts=stack(3, ()), has_ts=has_ts,
                rows=tuple(row_counts), leaves=tuple(leaves),
                runs=tuple(runs), nbytes=nbytes)

    # ---------------------------------------------------------------- launches
    def launch(self, pinned: PinnedShards, queries: np.ndarray,
               q_paas, ts_min: Optional[np.ndarray],
               bound: np.ndarray, *, k: int, mode: str = "auto",
               stats: Optional[SearchStats] = None):
        """One compiled mesh pass over a pinned generation.

        ``q_paas`` is the queries' ``[Q, w]`` PAA (a device array
        stays on the device); ``ts_min`` the per-shard ``[S]`` int32
        visibility cut or None; ``bound`` the per-query strict bsf (inf
        = unbounded) from the host-side buffer pool.  Returns host
        (dists [Q, k] f32, global ids [Q, k] int64 with -1 padding,
        counts [S, Q] int64), each read through ``merger.to_host`` and
        counted in ``stats``.
        """
        queries = np.atleast_2d(np.asarray(queries, np.float32))
        d, ids32, counts = ops.mesh_scan(
            jnp.asarray(queries),
            jnp.asarray(q_paas, jnp.float32),
            pinned.codes, pinned.raw, pinned.ids, pinned.ts,
            None if ts_min is None
            else jnp.asarray(np.asarray(ts_min, np.int32)),
            jnp.asarray(bound, jnp.float32), self.cfg,
            mesh=pinned.mesh, axis=self.axis, k=k, mode=mode)
        self._reg.counter("query.mesh_launches_total").inc()
        return (to_host(d, stats),
                to_host(ids32, stats).astype(np.int64),
                to_host(counts, stats).astype(np.int64))

    # ---------------------------------------------------------------- readouts
    @property
    def pinned(self) -> Optional[PinnedShards]:
        with self._lock:
            return self._pinned
