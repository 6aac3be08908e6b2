#!/usr/bin/env python3
"""Run the Coconut index's main path once on a TPU and check its answers.

    python chip_smoke.py [--seed 0]          # one chip, phases 0-3
    python chip_smoke.py --chips 4 [--seed 0]  # the four-chip mesh scan only

Phases (one chip):

0. device — a TPU is required (no CPU fallback), the kernels must
   dispatch to compiled Pallas, and the persistent compile cache is
   placed before the first compile.
1. static collection — 2^22 z-normalized random walks in the paper's
   shape (L=256, w=16, b=8, leaf 2000), made on the device and
   bulk-loaded with ``core.build``; exact and budgeted kNN probes
   (Q=16, k=10) checked against a device brute force over every row.
2. streaming + durability — 2^20 sliding windows (step 4) of a
   synthetic signal inserted in 16 batches into a concurrent
   ``CoconutLSM`` with a write-ahead log; windowed exact probes checked
   against a brute force over the window, then close, reopen through a
   ``TieredLeafStore`` and re-probe: the answers must not change.
3. sharded mesh scan — ``ShardedCoconutLSM(shards=4)`` over phase 2's
   rows: the one-launch mesh scan and the threaded fan-out must agree.

``--chips 4`` runs only the four-chip path: the phase-1 collection in a
four-shard engine whose pinned shard stacks span the four chips, so each
chip runs the compiled ``scan_verify`` kernel; its answers must equal
the threaded fan-out's and the brute force's.

Every phase raises on a failed check.  Earlier lines report rows, wall
and compile seconds and peak device bytes, for reading only.  The last
line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.chip import (CompileClock, require_tpu,  # noqa: E402
                               use_compile_cache)


@dataclass(frozen=True)
class Sizes:
    static_rows: int = 1 << 22
    stream_rows: int = 1 << 20
    batch: int = 1 << 16
    window: int = 1 << 18
    queries: int = 16
    k: int = 10


FULL = Sizes()
BRUTE_BLOCK = 1 << 13             # brute-force rows per step


# ----------------------------------------------------------------- reference
def pairwise_sq_dist(x: jax.Array, q: jax.Array) -> jax.Array:
    """Squared distances ``[Q, B]`` as the index defines them: f32
    differences, each square rounded on its own (the ``maximum`` keeps a
    compiler from fusing it into an add), then added pairwise, element i
    with element i + h for h = L/2, L/4, ..., 1 (L zero-padded to a power
    of two).  The order is fixed so the index's answers do not depend on
    the shape they were computed in; the reference adds in that order
    too, or the two would differ by a few ulp of rounding alone."""
    s = x[None, :, :] - q[:, None, :]
    s = jnp.maximum(s * s, 0.0)
    width = 1 << (s.shape[-1] - 1).bit_length()
    s = jnp.pad(s, ((0, 0), (0, 0), (0, width - s.shape[-1])))
    while width > 1:
        width //= 2
        s = s[..., :width] + s[..., width:]
    return s[..., 0]


@functools.partial(jax.jit, static_argnames=("k", "block"))
def brute_topk(rows: jax.Array, queries: jax.Array, *, k: int, block: int):
    """Exact k-NN by scanning every row: squared differences summed per
    block (:func:`pairwise_sq_dist`; no ``|x|^2 - 2xq + |q|^2`` expansion,
    whose default-precision matmul drops bits on the TPU) and a running
    top-k.  Returns (squared distances ``[Q, k]``, row indices
    ``[Q, k]``)."""
    n = rows.shape[0]
    nb = -(-n // block)
    if nb * block != n:
        rows = jnp.pad(rows, ((0, nb * block - n), (0, 0)))
    nq = queries.shape[0]

    def step(i, carry):
        best_d, best_i = carry
        x = jax.lax.dynamic_slice_in_dim(rows, i * block, block)
        d = pairwise_sq_dist(x, queries)                   # [Q, block]
        idx = i * block + jnp.arange(block, dtype=jnp.int32)
        d = jnp.where(idx[None, :] < n, d, jnp.inf)
        cat_d = jnp.concatenate([best_d, d], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(idx, (nq, block))], axis=1)
        neg, sel = jax.lax.top_k(-cat_d, k)
        return -neg, jnp.take_along_axis(cat_i, sel, axis=1)

    init = (jnp.full((nq, k), jnp.inf, jnp.float32),
            jnp.full((nq, k), -1, jnp.int32))
    return jax.lax.fori_loop(0, nb, step, init)


def max_ulp(a: np.ndarray, b: np.ndarray) -> int:
    """Largest distance in float32 ulps between two arrays of finite
    non-negative floats (their int32 views are then monotone)."""
    a = np.ascontiguousarray(a, np.float32).view(np.int32).astype(np.int64)
    b = np.ascontiguousarray(b, np.float32).view(np.int32).astype(np.int64)
    return int(np.abs(a - b).max())


def check_exact(name: str, d, ids, ref_d, ref_ids, ulps: int = 2) -> int:
    d, ids = np.asarray(d), np.asarray(ids, np.int64)
    ref_d, ref_ids = np.asarray(ref_d), np.asarray(ref_ids, np.int64)
    if not np.array_equal(ids, ref_ids):
        bad = np.nonzero((ids != ref_ids).any(axis=1))[0]
        raise AssertionError(
            f"{name}: ids differ from the brute force for queries "
            f"{bad.tolist()}: got {ids[bad[0]].tolist()}, "
            f"want {ref_ids[bad[0]].tolist()}")
    if not np.all(np.isfinite(d)):
        raise AssertionError(f"{name}: non-finite distances")
    u = max_ulp(d, ref_d)
    print(f"  {name}: ids match the brute force, max |d - ref| = {u} ulp",
          flush=True)
    if u > ulps:
        raise AssertionError(f"{name}: distances {u} ulp from the brute "
                             f"force (limit {ulps})")
    return u


def check_same(name: str, d, ids, want_d, want_ids) -> None:
    """Raise unless two answers are bit-identical; the message says
    whether the ids or only the distance bits differ, and by how much."""
    d, ids = np.asarray(d), np.asarray(ids)
    want_d, want_ids = np.asarray(want_d), np.asarray(want_ids)
    if np.array_equal(d, want_d) and np.array_equal(ids, want_ids):
        return
    same_ids = np.array_equal(ids, want_ids)
    raise AssertionError(
        f"{name}: answers differ ({'same ids' if same_ids else 'ids differ'}"
        f", max |d - want| = {max_ulp(d, want_d)} ulp): got ids "
        f"{ids.tolist()} d {d.tolist()}, want ids {want_ids.tolist()} "
        f"d {want_d.tolist()}")


# ------------------------------------------------------------------ reports
class Phase:
    """Times one phase and prints its line: rows, wall and compile
    seconds, and the running device peak."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock
        self.rows = 0

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.clock.read()
        print(f"phase {self.name}", flush=True)
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        wall = time.perf_counter() - self.t0
        secs, compiles, hits = (b - a for a, b in
                                zip(self.c0, self.clock.read()))
        peaks = [(dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for dv in jax.local_devices()]
        print(f"phase {self.name}: rows={self.rows} wall_s={wall:.3f} "
              f"compile_s={secs:.3f} compiles={compiles} "
              f"cache_hits={hits} peak_bytes_in_use={max(peaks)}",
              flush=True)
        return False


def counter(name: str) -> int:
    from repro.obs import get_registry
    return get_registry().counter(name).value


# ------------------------------------------------------------------- phases
def static_collection(sz: Sizes, seed: int):
    """Phase 1: bulk-load the paper-shaped collection and probe it."""
    from repro.configs.coconut_paper import INDEX, LEAF_SIZE
    from repro.core import tree as T
    from repro.data.series import query_workload, random_walk_blocks

    key = jax.random.PRNGKey(seed)
    raw = random_walk_blocks(jax.random.fold_in(key, 1), sz.static_rows,
                             INDEX.series_len,
                             block=min(sz.batch, sz.static_rows))
    queries = query_workload(jax.random.fold_in(key, 2), raw, sz.queries,
                             from_dataset_frac=1.0)
    # finished before the build is queued: the chip holds the collection
    # and its sorted copy, with little room for another pass's buffers
    ref_d, ref_i = jax.block_until_ready(
        brute_topk(raw, queries, k=sz.k, block=BRUTE_BLOCK))
    t0 = time.perf_counter()
    tree = T.build(raw, INDEX, leaf_size=LEAF_SIZE)
    jax.block_until_ready(tree.raw)
    print(f"  build: {tree.n} rows, {tree.n_leaves} leaves, "
          f"{time.perf_counter() - t0:.3f} s", flush=True)

    d, ids, st = T.exact_search_batch(tree, queries, k=sz.k)
    check_exact("exact_search_batch", d, ids, ref_d, ref_i)
    print(f"  exact: leaves scanned {st.leaves_scanned} of "
          f"{tree.n_leaves}, candidates {st.candidates}", flush=True)

    budget = max(1, tree.n_leaves // 10)
    bd, bids, bst = T.exact_search_batch(tree, queries, k=sz.k,
                                         budget=budget)
    kth, ref_kth = np.asarray(bd)[:, -1], np.asarray(ref_d)[:, -1]
    gap = np.asarray(bst.gap)
    # the same 2-ulp allowance as the exact check
    slack = 2 * np.spacing(ref_kth)
    if not np.all(ref_kth + slack >= kth - gap):
        raise AssertionError("budgeted: exact k-th distance below the "
                             "certified gap")
    if not np.all(kth + slack >= ref_kth):
        raise AssertionError("budgeted: k-th distance beats the exact one")
    print(f"  budgeted ({budget} leaves): max gap {float(gap.max()):.6g}, "
          f"exact answers {int(np.sum(gap == 0))}/{sz.queries}",
          flush=True)


def stream_windows(sz: Sizes, seed: int) -> jax.Array:
    from repro.configs.coconut_paper import INDEX
    from repro.data.series import sliding_windows, synthetic_signal
    step = 4
    total = (sz.stream_rows - 1) * step + INDEX.series_len
    sig = synthetic_signal(jax.random.fold_in(jax.random.PRNGKey(seed), 3),
                           total)
    return sliding_windows(sig, INDEX.series_len, step)


def streaming(sz: Sizes, seed: int, workdir: str):
    """Phase 2: WAL-backed concurrent ingest, windowed probes, reopen."""
    from repro.configs.coconut_paper import INDEX, LEAF_SIZE
    from repro.core.lsm import CoconutLSM
    from repro.data.series import query_workload
    from repro.kernels import ops
    from repro.storage.store import SegmentStore
    from repro.storage.tiers import TieredLeafStore

    wins = stream_windows(sz, seed)
    n = wins.shape[0]
    lo = n - sz.window
    queries = query_workload(jax.random.fold_in(jax.random.PRNGKey(seed), 4),
                             wins[lo:], sz.queries, from_dataset_frac=1.0)
    ref_d, ref_i = brute_topk(wins[lo:], queries, k=sz.k, block=BRUTE_BLOCK)
    ref_i = np.asarray(ref_i, np.int64) + lo
    host = np.asarray(wins)
    queries = np.asarray(queries)

    lsm = CoconutLSM(INDEX, buffer_capacity=sz.batch, leaf_size=LEAF_SIZE,
                     concurrent=True, store=SegmentStore(workdir),
                     wal_fsync="commit")
    t0 = time.perf_counter()
    for b0 in range(0, n, sz.batch):
        lsm.insert(host[b0:b0 + sz.batch])
    lsm.flush()
    print(f"  ingest: {lsm.n} rows in {len(lsm.runs)} runs, "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    d, ids, _ = lsm.search_exact_batch(queries, k=sz.k, window=sz.window)
    check_exact("windowed exact", d, ids, ref_d, ref_i)
    lsm.close()

    cache = TieredLeafStore(256 << 20)
    lsm = CoconutLSM.open(workdir, tiers=cache)
    packed = ops.mindist_batch_packed
    launches = 0

    def counted(*args, **kw):
        nonlocal launches
        launches += 1
        return packed(*args, **kw)

    ops.mindist_batch_packed = counted      # counts the packed-path launches
    try:
        d2, ids2, _ = lsm.search_exact_batch(queries, k=sz.k,
                                             window=sz.window)
        # one probe per query: a distinct result-cache key each, so the
        # packed leaf blocks keep getting touched and the hot ones move
        # to the device
        for qi in range(sz.queries):
            dq, iq, _ = lsm.search_exact_batch(queries[qi:qi + 1], k=sz.k,
                                               window=sz.window)
            check_same(f"reopened: query {qi} alone", dq[0], iq[0],
                       d[qi], ids[qi])
    finally:
        ops.mindist_batch_packed = packed
    check_same("reopened index", d2, ids2, d, ids)
    print(f"  reopened: answers identical; packed-path launches "
          f"{launches}, cache.promotions {cache.promotions}", flush=True)
    if launches == 0:
        raise AssertionError("reopened probes never ran the packed path")
    if cache.promotions == 0:
        raise AssertionError("no leaf block was promoted to the device")
    lsm.close()
    return host, queries, d, ids


def sharded_mesh(sz: Sizes, host: np.ndarray, queries: np.ndarray,
                 want_d: np.ndarray, want_ids: np.ndarray):
    """Phase 3: four shards on this chip, mesh launch vs threaded."""
    from repro.configs.coconut_paper import INDEX, LEAF_SIZE
    from repro.distributed.sharded_lsm import ShardedCoconutLSM

    idx = ShardedCoconutLSM(INDEX, shards=4, buffer_capacity=sz.batch,
                            leaf_size=LEAF_SIZE)
    for b0 in range(0, len(host), sz.batch):
        idx.insert(host[b0:b0 + sz.batch])
    idx.flush()
    print(f"  ingest: {idx.n} rows, shard sizes {idx.shard_sizes()}",
          flush=True)
    fb0 = counter("query.mesh_fallbacks_total")
    l0 = counter("query.mesh_launches_total")
    for window in (sz.window, None):
        md, mi, _ = idx.search_exact_batch(queries, k=sz.k, window=window,
                                           scan_mode="mesh")
        td, ti, _ = idx.search_exact_batch(queries, k=sz.k, window=window,
                                           scan_mode="threaded")
        check_same(f"mesh vs threaded (window={window})", md, mi, td, ti)
        if window is not None:
            check_same("sharded vs the single engine", md, mi, want_d,
                       want_ids)
    fallbacks = counter("query.mesh_fallbacks_total") - fb0
    launches = counter("query.mesh_launches_total") - l0
    pinned = idx._mesh_engine_get().pinned
    print(f"  mesh == threaded; launches {launches}, fallbacks "
          f"{fallbacks}, mesh devices {pinned.mesh.devices.size}",
          flush=True)
    if fallbacks or launches != 2:
        raise AssertionError("unbudgeted probes left the mesh path")
    idx.close()


def four_chips(sz: Sizes, seed: int, clock: CompileClock):
    """``--chips 4``: the phase-1 collection sharded over four chips."""
    from repro.configs.coconut_paper import INDEX, LEAF_SIZE
    from repro.distributed.sharded_lsm import ShardedCoconutLSM
    from repro.data.series import query_workload, random_walk_blocks
    from repro.kernels import mesh_scan

    with Phase("4chips", clock) as ph:
        key = jax.random.PRNGKey(seed)
        raw = random_walk_blocks(jax.random.fold_in(key, 1),
                                 sz.static_rows, INDEX.series_len,
                                 block=min(sz.batch, sz.static_rows))
        queries = query_workload(jax.random.fold_in(key, 2), raw,
                                 sz.queries, from_dataset_frac=1.0)
        ref_d, ref_i = jax.block_until_ready(
            brute_topk(raw, queries, k=sz.k, block=BRUTE_BLOCK))
        host, queries = np.asarray(raw), np.asarray(queries)
        del raw
        ph.rows = len(host)

        idx = ShardedCoconutLSM(INDEX, shards=4, buffer_capacity=sz.batch,
                                leaf_size=LEAF_SIZE, scan_mode="mesh")
        t0 = time.perf_counter()
        for b0 in range(0, len(host), sz.batch):
            idx.insert(host[b0:b0 + sz.batch])
        idx.flush()
        print(f"  ingest: {idx.n} rows, shard sizes {idx.shard_sizes()}, "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        fb0 = counter("query.mesh_fallbacks_total")
        md, mi, _ = idx.search_exact_batch(queries, k=sz.k)
        if counter("query.mesh_fallbacks_total") != fb0:
            raise AssertionError("the probe left the mesh path")
        pinned = idx._mesh_engine_get().pinned
        devs = {s.device for s in pinned.raw.addressable_shards}
        print(f"  pinned stacks: {pinned.raw.shape} over {len(devs)} "
              f"devices, {pinned.nbytes} bytes", flush=True)
        if len(devs) != 4 or pinned.mesh.devices.size != 4:
            raise AssertionError("pinned stacks do not span 4 devices")
        # the launch that just ran, compiled again from its cache entry:
        # one shard per device, so the body is the Pallas kernel
        fn = mesh_scan.mesh_scan_launch(pinned.mesh, "shard", INDEX,
                                        k=sz.k, ts_filter=False,
                                        mode="pallas")
        text = fn.lower(pinned.codes, pinned.raw, pinned.ids, pinned.ts,
                        jnp.zeros(4, jnp.int32), jnp.asarray(queries),
                        jnp.zeros((sz.queries, INDEX.segments)),
                        jnp.zeros(sz.queries)).compile().as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError("the mesh launch holds no Pallas kernel")
        check_exact("mesh exact", md, mi, ref_d, ref_i)
        td, ti, _ = idx.search_exact_batch(queries, k=sz.k,
                                           scan_mode="threaded")
        check_same("mesh vs threaded", md, mi, td, ti)
        print("  mesh == threaded == brute force", flush=True)
        idx.close()


def run(sz: Sizes, seed: int, clock: CompileClock) -> None:
    """Phases 1-3 at sizes ``sz``."""
    with Phase("1 static", clock) as ph:
        ph.rows = sz.static_rows
        static_collection(sz, seed)
    with Phase("2 streaming", clock) as ph:
        ph.rows = sz.stream_rows
        with tempfile.TemporaryDirectory(prefix="coconut-smoke-") as wd:
            host, queries, d, ids = streaming(sz, seed, wd)
    with Phase("3 sharded mesh", clock) as ph:
        ph.rows = sz.stream_rows
        sharded_mesh(sz, host, queries, d, ids)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    clock = CompileClock()
    t0 = time.perf_counter()
    with Phase("0 device", clock):
        cache_dir = use_compile_cache(ROOT / ".jax_cache")
        dev = require_tpu()
        from repro.kernels import ops
        mode = ops._default_mode()
        print(f"  device: {dev['platform']} {dev['kind']} x{dev['count']}; "
              f"kernel mode {mode}; compile cache {cache_dir}", flush=True)
        if mode != "pallas":
            raise AssertionError(f"kernels resolve to {mode!r}, not "
                                 "compiled Pallas (COCONUT_KERNEL_MODE?)")
        if dev["count"] < args.chips:
            raise AssertionError(f"--chips {args.chips} needs "
                                 f"{args.chips} devices")
    if args.chips == 4:
        four_chips(FULL, args.seed, clock)
    else:
        run(FULL, args.seed, clock)
    secs, compiles, hits = clock.read()
    print(f"total: wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={secs:.3f} compiles={compiles} cache_hits={hits}",
          flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
