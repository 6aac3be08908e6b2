"""The one traffic generator of the benchmark.

A traffic mix is a data file (``traffic/<mix>.json``) whose ``"loop"``
names one of the loops below; the rest of the file is the loop's
parameters.  Every loop is closed: one client that waits for each reply
before it sends the next call.  The system has no request queue, so an
open loop would have to invent the batching that belongs to the program.

A loop runs in four steps, each called by the harness:

1. ``setup()``: make the cell's data on the device from the seed (or
   from the mix's fixed data key), build the program's state, and warm
   up every shape the window will use.
2. ``window(seconds, annotate)``: whole calls, back to back.  The window
   runs from the first call's start to the end of the first call that
   finishes ``seconds`` or more after it (for the probe mixes, the end of
   a whole pass through the query pool).
3. ``release()``: drop the program's state, once the device's peak
   memory has been read.
4. ``check()``: compare what the window's calls produced with the plain
   reference (:mod:`refknn`), and return each compared number beside its
   limit (from the mix's ``"limits"``).
"""
from __future__ import annotations

import contextlib
import gc
import os
import shutil
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import refknn
import seriesgen
from chipenv import key_from_seed

# ids of the generators' keys, folded into the seed's key
_DATA, _POOL, _WARM, _CHECK, _SAMPLE, _ORDER = 1, 2, 3, 4, 5, 6


def _no_annotation(name: str):
    return contextlib.nullcontext()


class Loop:
    """State and records shared by every loop."""

    def __init__(self, config: dict, traffic: dict, seed: int, system,
                 workdir: Optional[Path] = None):
        self.config, self.traffic = config, traffic
        self.system = system
        self.workdir = workdir
        self.key = key_from_seed(seed)
        self.calls: List[dict] = []        # one record per call
        self.failed = 0
        self.errors: List[str] = []
        self.phases: Dict[str, float] = {}     # seconds of set-up steps
        self._t = time.perf_counter()

    def _phase(self, name: str) -> None:
        now = time.perf_counter()
        self.phases[name] = now - self._t
        self._t = now

    def _run_window(self, seconds: float, call: Callable[[int], dict],
                    annotate, done: Callable[[], bool] = lambda: True,
                    name: str = "bench.call") -> None:
        """Call ``call(i)`` for i = 0, 1, ... until a call ends at least
        ``seconds`` after the first began and ``done()`` holds.  A call
        that raises counts as failed; the window stops after 3 such."""
        t_start = None
        i = 0
        while True:
            t0 = time.perf_counter()
            if t_start is None:
                t_start = t0
            try:
                with annotate(name):
                    rec = call(i)
            except Exception as e:            # counted, reported, judged
                self.failed += 1
                self.errors.append(f"call {i}: {type(e).__name__}: {e}")
                rec = {"error": True}
            t1 = time.perf_counter()
            self.calls.append(dict(rec, t0=t0, t1=t1, i=i))
            i += 1
            if self.failed >= 3 or (t1 - t_start >= seconds and done()):
                break

    @property
    def window_bounds(self):
        return self.calls[0]["t0"], self.calls[-1]["t1"]

    @property
    def attempted(self) -> int:
        return len(self.calls)

    def release(self) -> None:
        gc.collect()


def _limits(traffic: dict, values: Dict[str, float]) -> Dict[str, dict]:
    lim = traffic["limits"]
    return {n: {"value": v, "limit": lim[n]} for n, v in values.items()}


# --------------------------------------------------------------- probes
class ProbeLoop(Loop):
    """k-NN probes of a static collection, cycling a query pool.

    Traffic keys: ``queries_per_call``, ``k``, ``pool_calls`` (calls in
    one pass of the pool), ``noise`` (of the noisy-member queries),
    ``budget_leaf_share`` (null: exact; else the share of the leaves a
    budgeted probe may scan), ``warmup_calls`` (calls on a pool of their
    own, made in the set-up to compile the shapes the window will use),
    ``data_key`` (the collection and the pools are made from this fixed
    key, and the run's seed orders the pool: how much work a query takes
    depends on the data, so every seed runs the same work in another
    order).  The window ends on a whole pass of the pool."""

    def setup(self) -> None:
        c, t = self.config, self.traffic
        L, q = c["series_len"], t["queries_per_call"]
        data = key_from_seed(t["data_key"])
        self.raw = seriesgen.random_walk_blocks(
            jax.random.fold_in(data, _DATA), c["series"], L,
            c["make_block"])
        pool = seriesgen.noisy_members(
            jax.random.fold_in(data, _POOL), self.raw,
            t["pool_calls"] * q, t["noise"])
        warm = seriesgen.noisy_members(
            jax.random.fold_in(data, _WARM), self.raw,
            t["warmup_calls"] * q, t["noise"])
        order = jax.random.permutation(
            jax.random.fold_in(self.key, _ORDER), t["pool_calls"] * q)
        self.pool = np.asarray(pool[order]).reshape(t["pool_calls"], q, L)
        warm = np.asarray(warm).reshape(t["warmup_calls"], q, L)
        self._phase("data_s")
        self.state = self.system.build(self.raw)
        self._phase("build_s")
        n_leaves = -(-c["series"] // c["leaf_size"])
        share = t["budget_leaf_share"]
        self.budget = None if share is None else max(1, int(n_leaves
                                                            * share))
        for w in warm:
            self.system.search(self.state, w, k=t["k"], budget=self.budget)
        self._phase("warmup_s")

    def window(self, seconds: float, annotate=_no_annotation) -> None:
        t = self.traffic
        npool = len(self.pool)

        def call(i: int) -> dict:
            p = i % npool
            d, ids, counts, gap = self.system.search(
                self.state, self.pool[p], k=t["k"], budget=self.budget)
            return {"pool": p, "queries": len(self.pool[p]), "d": d,
                    "ids": ids, "gap": gap, "counts": counts}

        self._run_window(seconds, call, annotate,
                         done=lambda: len(self.calls) % npool == 0)

    def release(self) -> None:
        self.state = None
        super().release()

    def check(self) -> dict:
        t = self.traffic
        k = t["k"]
        ok = [c for c in self.calls if not c.get("error")]
        used = sorted({c["pool"] for c in ok})
        L = self.pool.shape[-1]
        ref_d, ref_i = refknn.brute_topk_blocked(
            self.raw, self.pool[used].reshape(-1, L), k=k)
        q = self.pool.shape[1]
        row = {p: j for j, p in enumerate(used)}
        ref_d = ref_d.reshape(len(used), q, k)
        ref_i = ref_i.reshape(len(used), q, k)
        out: Dict[str, float] = {}
        extra: Dict[str, float] = {}
        if t["budget_leaf_share"] is None:
            wrong, worst = 0, 0
            for c in ok:
                j = row[c["pool"]]
                wrong += int((c["ids"] != ref_i[j]).sum())
                worst = max(worst, refknn.max_ulp(c["d"], ref_d[j]))
            out = {"ids_wrong": wrong, "dist_ulp": worst}
        else:
            # every returned distance is the true distance of the row the
            # answer names; no k-th distance beats the exact one; the
            # exact one lies within the certified gap
            qs = np.concatenate([self.pool[c["pool"]] for c in ok])
            ids = np.concatenate([c["ids"] for c in ok])
            true_d = refknn.distances_of(self.raw, qs, ids)
            got_d = np.concatenate([c["d"] for c in ok])
            gap = np.concatenate([c["gap"] for c in ok])
            kth_ref = np.concatenate([ref_d[row[c["pool"]], :, -1]
                                      for c in ok])
            ulp_lim = t["limits"]["dist_ulp"]
            slack = (ulp_lim + 1) * np.spacing(kth_ref)
            kth = got_d[:, -1]
            dup = np.array([len(np.unique(r)) < len(r) for r in ids])
            bad = (dup | (ids < 0).any(axis=1)
                   | (kth < kth_ref - slack)
                   | (kth - gap > kth_ref + slack))
            out = {"dist_ulp": refknn.max_ulp(got_d, true_d),
                   "bad_answers": int(bad.sum())}
            first = [c for c in ok if c["i"] < len(self.pool)]
            hits = [len(np.intersect1d(c["ids"][qi],
                                       ref_i[row[c["pool"]], qi]))
                    for c in first for qi in range(q)]
            if len(first) == len(self.pool):
                extra["recall_at_10"] = float(np.mean(hits)) / k
        return {"checks": _limits(t, out), "extra": extra}


# --------------------------------------------------------------- ingest
class IngestLoop(Loop):
    """Rounds of WAL-backed inserts into a fresh streaming engine.

    Each round removes the previous round's directories, creates an empty
    engine in a new one, inserts the deployment's ``rows_per_round``
    sliding windows in batches of ``batch`` rows (each acknowledged on
    return), takes a crash image of the store, drains with ``flush()``
    and closes.  The drain is inside the window, so no work can be pushed
    past it.  Traffic keys: ``batch``, ``readback_rows`` (acknowledged
    rows probed back after the window), ``check_queries`` and ``k``
    (exact probes against the reference), ``noise``."""

    def setup(self) -> None:
        c, t = self.config, self.traffic
        rows = seriesgen.sliding_windows(
            jax.random.fold_in(self.key, _DATA), c["rows_per_round"],
            c["series_len"], c["window_step"])
        self.queries = np.asarray(seriesgen.noisy_members(
            jax.random.fold_in(self.key, _CHECK), rows,
            t["check_queries"], t["noise"]))
        self.rows = np.asarray(rows)
        del rows
        self._phase("data_s")
        # the same device programs, without the store and the log: the
        # warm-up writes nothing to disk
        self._round(None, record={"acks": []})
        self._phase("warmup_s")
        self.workdir.mkdir(parents=True, exist_ok=True)

    def _round(self, root: Optional[Path], record: dict,
               annotate=_no_annotation) -> None:
        b = self.traffic["batch"]
        eng = self.system.create(None if root is None else str(root))
        try:
            for s in range(0, len(self.rows), b):
                t0 = time.perf_counter()
                with annotate("bench.insert"):
                    eng.insert(self.rows[s:s + b])
                record["acks"].append((t0, time.perf_counter(),
                                       len(self.rows[s:s + b])))
            if root is not None:
                record["crash"] = _crash_image(root)
                record["unbuilt"] = self.system.unbuilt_rows(eng)
            with annotate("bench.drain"):
                eng.flush()
            record["wal_appends"] = self.system.wal_appends(eng)
        finally:
            eng.close()

    def window(self, seconds: float, annotate=_no_annotation) -> None:
        self.last_root: Optional[Path] = None

        def call(i: int) -> dict:
            root = self.workdir / f"round-{i}"
            if self.last_root is not None:
                shutil.rmtree(self.last_root)
                shutil.rmtree(_image_of(self.last_root), ignore_errors=True)
            self.last_root = root
            rec: dict = {"acks": []}
            self._round(root, rec, annotate)
            return dict(rec, rows=sum(a[2] for a in rec["acks"]))

        self._run_window(seconds, call, annotate, name="bench.round")

    def _read_back(self, root: Path, sample: np.ndarray) -> dict:
        """Reopen ``root`` as a restart would and read the acknowledged
        rows back: how many rows it holds, how many it replayed from the
        log, the sampled rows not found by id at distance 0, and exact
        probes of every row."""
        k = self.traffic["k"]
        eng, replayed = self.system.reopen(str(root))
        try:
            n = int(eng.n)
            eng.flush()              # build the replayed tail into a run
            d1, i1 = self.system.search(eng, self.rows[sample], k=1)
            d, ids = self.system.search(eng, self.queries, k=k)
        finally:
            eng.close()
            del eng
            gc.collect()
        return {"n": n, "replayed": replayed,
                "readback_wrong": int(((i1[:, 0] != sample)
                                       | (d1[:, 0] != 0)).sum()),
                "d": d, "ids": ids}

    def check(self) -> dict:
        t = self.traffic
        last = self.calls[-1]
        acked = sum(a[2] for a in last.get("acks", []))
        rng = np.random.default_rng(
            np.asarray(jax.random.key_data(
                jax.random.fold_in(self.key, _SAMPLE))).tolist())
        n = len(self.rows)
        tail = 16
        sample = np.concatenate([
            rng.choice(n - tail, t["readback_rows"] - tail, replace=False),
            np.arange(n - tail, n)])
        t0 = time.perf_counter()
        # the store as a crash after the last ack would leave it, and as
        # the drain and close left it
        crash = self._read_back(last["crash"], sample)
        drained = self._read_back(self.last_root, sample)
        self.phases["reopen_s"] = time.perf_counter() - t0
        ref_d, ref_i = refknn.brute_topk_blocked(
            jnp.asarray(self.rows), self.queries, k=t["k"])
        both = (crash, drained)
        unbuilt = last["unbuilt"]
        out = {"rows_missing": max(abs(acked - r["n"]) for r in both),
               "readback_wrong": sum(r["readback_wrong"] for r in both),
               "ids_wrong": sum(int((r["ids"] != ref_i).sum())
                                for r in both),
               "dist_ulp": max(refknn.max_ulp(r["d"], ref_d)
                               for r in both),
               # a crash image with no unbuilt tail leaves the log unread
               "wal_replay_short": (unbuilt - crash["replayed"]
                                    if unbuilt else acked),
               "wal_appends_off": abs(last["wal_appends"]
                                      - len(last["acks"]))}
        return {"checks": _limits(t, out), "extra": {}}


def _image_of(root: Path) -> Path:
    return root.with_name(root.name + ".crash")


def _crash_image(root: Path) -> Path:
    """Hard links to every file of the store in ``root`` as it stands:
    what a crash now would leave on disk.  The engine never writes a file
    again after its last append (segments are written once, the manifest
    is replaced by a rename, the log is rotated into a fresh file at the
    next commit), so the links keep the files as they are now, and making
    them writes no data."""
    image = _image_of(root)
    image.mkdir()
    for f in os.scandir(root):
        if not f.is_file():
            raise RuntimeError(f"{f.path}: not a file of the store")
        os.link(f.path, image / f.name)
    return image


# ---------------------------------------------------------------- build
class BuildLoop(Loop):
    """Repeated bulk loads of the device-resident collection into a
    queryable tree, each waited for; the previous tree is dropped before
    the next build.  Traffic keys: ``check_queries``, ``k``, ``noise``
    (exact probes of the last tree against the reference)."""

    def setup(self) -> None:
        c, t = self.config, self.traffic
        self.raw = seriesgen.random_walk_blocks(
            jax.random.fold_in(self.key, _DATA), c["series"],
            c["series_len"], c["make_block"])
        self.queries = np.asarray(seriesgen.noisy_members(
            jax.random.fold_in(self.key, _CHECK), self.raw,
            t["check_queries"], t["noise"]))
        self._phase("data_s")
        self.state = self.system.build(self.raw)
        self.state = None
        self._phase("warmup_s")

    def window(self, seconds: float, annotate=_no_annotation) -> None:
        def call(i: int) -> dict:
            self.state = None
            self.state = self.system.build(self.raw)
            return {"rows": int(self.raw.shape[0])}

        self._run_window(seconds, call, annotate, name="bench.build")

    def check(self) -> dict:
        t = self.traffic
        keys, traw, offs = self.system.layout(self.state)
        offs_h = np.asarray(offs, np.int64)
        n = self.raw.shape[0]
        perm_bad = int(len(offs_h) != n or (offs_h < 0).any()
                       or (offs_h >= n).any()
                       or (np.bincount(np.clip(offs_h, 0, n - 1),
                                       minlength=n) != 1).any())
        misplaced = perm_bad * n + _rows_differ(traw, self.raw, offs)
        unsorted = _keys_unsorted(np.asarray(keys))
        d, ids, _, _ = self.system.search(self.state, self.queries,
                                          k=t["k"], budget=None)
        del keys, traw, offs
        self.state = None
        gc.collect()
        ref_d, ref_i = refknn.brute_topk_blocked(self.raw, self.queries,
                                                 k=t["k"])
        out = {"rows_misplaced": misplaced, "keys_unsorted": unsorted,
               "ids_wrong": int((ids != ref_i).sum()),
               "dist_ulp": refknn.max_ulp(d, ref_d)}
        return {"checks": _limits(t, out), "extra": {}}


@jax.jit
def _block_differs(a, raw, idx):
    b = raw[idx]
    ab = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bb = jax.lax.bitcast_convert_type(b, jnp.uint32)
    return jnp.sum(jnp.any(ab != bb, axis=1))


def _rows_differ(traw, raw, offs, block: int = 1 << 16) -> int:
    """Rows of the tree whose bits differ from the collection's row that
    the tree says they are."""
    n = traw.shape[0]
    offs = jnp.clip(jnp.asarray(offs, jnp.int32), 0, raw.shape[0] - 1)
    bad = 0
    for s in range(0, n, block):
        e = min(n, s + block)
        bad += int(_block_differs(traw[s:e], raw, offs[s:e]))
    return bad


def _keys_unsorted(keys: np.ndarray) -> int:
    """Adjacent pairs of multi-word keys out of lexicographic order."""
    a, b = keys[:-1], keys[1:]
    decided = np.zeros(len(a), bool)
    greater = np.zeros(len(a), bool)
    for w in range(keys.shape[1]):
        gt = (a[:, w] > b[:, w]) & ~decided
        greater |= gt
        decided |= a[:, w] != b[:, w]
    return int(greater.sum())


LOOPS = {"probe": ProbeLoop, "ingest": IngestLoop, "build": BuildLoop}
