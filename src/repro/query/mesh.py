"""MeshScanEngine: pinned device-sharded shard columns + one-launch scan.

The residency half of the device-resident sharded scan
(:mod:`repro.kernels.mesh_scan` is the compute half).  The engine owns:

* **Pinning** — stacking every shard's immutable run columns (SAX codes,
  raw series, global ids, timestamps) into ``[S, cap, ...]`` arrays
  padded to a bucket-rounded capacity and ``device_put`` with a
  ``PartitionSpec('shard', ...)`` layout on a 1-D scan mesh, so a probe
  batch launches with zero host->device column traffic.
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

Bit-parity protocol: the repo's canonical distance bits are those of
``summarization.sum_sq``, which adds a row's squares in one fixed
pairwise order of elementwise ops, so the bits never depend on the
shape a row was verified in.  The launch adds in that order too, but
its on-device distances are still treated as *selection* scores only:
after the launch picks each query's top-k rows,
:meth:`MeshScanEngine.launch` re-verifies exactly those rows with the
eager verifier (``summarization.sq_dist``), so the values are the ones
the threaded executor returns for the same rows whatever the kernel's
compiler did.  Selection itself can only differ from the threaded path
when two rows' true distances sit within one ulp — the same
measure-zero tie class both paths already carry.
"""
from __future__ import annotations

import dataclasses
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
from .planner import DeviceLayout, build_device_layout

__all__ = ["MeshScanEngine", "PinnedShards"]

_I32 = np.iinfo(np.int32)


@dataclasses.dataclass(frozen=True)
class PinnedShards:
    """One immutable pinned generation: the device mirror of one exact
    run-set.  Strong ``runs`` refs keep every mirrored tree alive so the
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
    # host mirror for the eager re-verification of selected candidates
    # (+ the id -> flat-slot lookup): [S*cap, L] rows, ids sorted with
    # their argsort so a searchsorted maps global id -> pinned slot
    host_raw: np.ndarray
    ids_sorted: np.ndarray
    id_order: np.ndarray


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
        """The pinned generation mirroring ``snaps`` (one Snapshot per
        shard), rebuilding if any shard's run set changed.  Returns
        None when the snapshot cannot be pinned (ids missing or outside
        int32, or the pin budget would be exceeded) — the caller must
        fall back to the threaded path."""
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
            shards, runs, has_ts = [], [], True
            for sn in snaps:
                codes_l, raw_l, ids_l, ts_l, leaves = [], [], [], [], 0
                for r in sn.runs:
                    t = r.tree
                    if t.ids is None:
                        return None
                    ids_np = np.asarray(t.ids)
                    if ids_np.size and (int(ids_np.min()) < 0
                                        or int(ids_np.max()) > _I32.max):
                        return None
                    codes_l.append(np.asarray(t.codes, np.uint8))
                    if t.raw is not None:
                        raw_np = np.asarray(t.raw, np.float32)
                    else:
                        raw_np = np.asarray(t.raw_ref, np.float32)[
                            np.asarray(t.offsets)]
                    raw_l.append(raw_np)
                    ids_l.append(ids_np.astype(np.int32))
                    if t.timestamps is None:
                        has_ts = False
                        ts_l.append(np.zeros(t.n, np.int32))
                    else:
                        ts_l.append(np.asarray(t.timestamps, np.int32))
                    leaves += t.n_leaves
                    runs.append(r)
                shards.append((codes_l, raw_l, ids_l, ts_l, leaves))
            row_counts = [sum(len(i) for i in sh[2]) for sh in shards]
            mesh = make_scan_mesh(len(snaps), axis=self.axis)
            layout = build_device_layout(
                row_counts, n_devices=mesh.devices.size,
                bucket=self.bucket)
            s, cap = layout.n_shards, layout.cap
            nbytes = s * cap * (w + 4 * L + 4 + 4)
            if self.max_pin_bytes is not None \
                    and nbytes > self.max_pin_bytes:
                return None
            codes = np.zeros((s, cap, w), np.uint8)
            raw = np.zeros((s, cap, L), np.float32)
            ids = np.full((s, cap), -1, np.int32)
            ts = np.zeros((s, cap), np.int32)
            for si, (codes_l, raw_l, ids_l, ts_l, _lv) in \
                    enumerate(shards):
                at = 0
                for c, rw, i, tcol in zip(codes_l, raw_l, ids_l, ts_l):
                    n = len(i)
                    codes[si, at:at + n] = c
                    raw[si, at:at + n] = rw
                    ids[si, at:at + n] = i
                    ts[si, at:at + n] = tcol
                    at += n
            spec3 = NamedSharding(mesh, P(self.axis, None, None))
            spec2 = NamedSharding(mesh, P(self.axis, None))
            host_raw = raw.reshape(s * cap, L)
            ids_flat = ids.reshape(s * cap).astype(np.int64)
            id_order = np.argsort(ids_flat, kind="stable")
            return PinnedShards(
                fingerprint=fp, layout=layout, mesh=mesh,
                codes=jax.device_put(codes, spec3),
                raw=jax.device_put(raw, spec3),
                ids=jax.device_put(ids, spec2),
                ts=jax.device_put(ts, spec2),
                has_ts=has_ts,
                rows=tuple(row_counts),
                leaves=tuple(sh[4] for sh in shards),
                runs=tuple(runs), nbytes=nbytes,
                host_raw=host_raw,
                ids_sorted=ids_flat[id_order], id_order=id_order)

    # ---------------------------------------------------------------- launches
    def launch(self, pinned: PinnedShards, queries: np.ndarray,
               q_paas: np.ndarray, ts_min: Optional[np.ndarray],
               bound: np.ndarray, *, k: int, mode: str = "auto"):
        """One compiled mesh pass over a pinned generation.

        ``ts_min`` is the per-shard ``[S]`` int32 visibility cut or
        None; ``bound`` the per-query strict bsf (inf = unbounded) from
        the host-side buffer pool.  Returns host (dists [Q, k] f32,
        global ids [Q, k] int64 with -1 padding, counts [S, Q] int64).
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
        d = np.asarray(d).copy()
        ids64 = np.asarray(ids32, np.int64)
        # canonical bits: the launch SELECTED these rows; their reported
        # distances are re-verified with the eager op chain (the bits
        # every threaded entry point returns — see module docstring)
        valid = ids64 >= 0
        if valid.any():
            qi, _ki = np.nonzero(valid)
            pos = np.searchsorted(pinned.ids_sorted, ids64[valid])
            slot = pinned.id_order[pos]
            rows = jnp.asarray(pinned.host_raw[slot])
            d[valid] = np.asarray(S.sq_dist(rows, jnp.asarray(queries[qi])),
                                  np.float32)
            # keep each query's pool sorted after the re-verification
            # (stable: sub-ulp rank flips keep the launch's order)
            sel = np.argsort(d, axis=1, kind="stable")
            d = np.take_along_axis(d, sel, axis=1)
            ids64 = np.take_along_axis(ids64, sel, axis=1)
        return d, ids64, np.asarray(counts, np.int64)

    # ---------------------------------------------------------------- readouts
    @property
    def pinned(self) -> Optional[PinnedShards]:
        with self._lock:
            return self._pinned
