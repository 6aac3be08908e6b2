"""Rounds of acknowledged, WAL-backed inserts into a streaming engine."""
from __future__ import annotations

import gc
import os
import shutil
import time
from pathlib import Path
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

import refknn
import seriesgen
from loops import (_CHECK, _DATA, _SAMPLE, Loop, _limits,
                   _no_annotation)


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

    def info(self, counters: Dict[str, float]) -> dict:
        """Seconds inside the inserts, and the write path's counters."""
        acks = [a for c in self.calls for a in c.get("acks", ())]
        return {"insert_s": sum(b - a for a, b, _ in acks),
                "counters": {k: v for k, v in counters.items()
                             if k.startswith(("compact.", "ingest.wal",
                                              "ingest.backpressure",
                                              "io.bytes"))
                             and not k.endswith(("p50", "p95", "p99"))}}


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


LOOP = IngestLoop
