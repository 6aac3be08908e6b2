"""The streaming LSM engine with its write-ahead log and segment store:
the ``ingest`` loop."""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from system import bf16_round, summary_config


class StreamIndex:
    """The streaming LSM engine with its write-ahead log and segment
    store, as the deployment configures it."""

    def __init__(self, config: dict):
        self.config = config
        self.cfg = summary_config(config)

    def create(self, root: Optional[str]):
        """A fresh engine writing to ``root`` (None: no store and no
        log, which warms up the same device programs without writing)."""
        from repro.core.lsm import CoconutLSM
        from repro.storage.store import SegmentStore
        c = self.config
        return CoconutLSM(self.cfg, buffer_capacity=c["buffer_capacity"],
                          leaf_size=c["leaf_size"],
                          size_ratio=c["size_ratio"], mode=c["mode"],
                          concurrent=c["concurrent"],
                          store=None if root is None else SegmentStore(root),
                          wal_fsync=c["wal_fsync"])

    @staticmethod
    def unbuilt_rows(engine) -> int:
        """Rows acknowledged but not yet built into a run: they live in
        the buffer and, with a store, in the write-ahead log alone."""
        return int(engine.ingest_lag())

    @staticmethod
    def wal_appends(engine) -> int:
        """Records the engine appended to its write-ahead log."""
        return int(engine.ingest.get("wal_appends"))

    @staticmethod
    def reopen(root: str):
        """Reopen a store as a restart does: its committed runs, and the
        log's tail replayed into the buffer.  Returns (engine, rows
        replayed from the log)."""
        from repro.core.lsm import CoconutLSM
        engine = CoconutLSM.open(root)
        return engine, int(engine.ingest.get("wal_replayed_rows"))

    @staticmethod
    def search(engine, queries: np.ndarray, *, k: int
               ) -> Tuple[np.ndarray, np.ndarray]:
        d, ids, _ = engine.search_exact_batch(queries, k=k)
        return np.asarray(d, np.float32), np.asarray(ids, np.int64)


class StreamControl(StreamIndex):
    """The program's engine fed the rows rounded to bfloat16."""

    def create(self, root):
        return _RoundingWriter(super().create(root))


class _RoundingWriter:
    """The engine, with every insert rounded first."""

    def __init__(self, eng):
        self.eng = eng

    def insert(self, rows):
        self.eng.insert(np.asarray(bf16_round(rows)))

    def __getattr__(self, name):
        return getattr(self.eng, name)


def system(config: dict) -> StreamIndex:
    return StreamIndex(config)


def control(config: dict, traffic: dict) -> StreamIndex:
    return StreamControl(config)


def cpu_size(config: dict, traffic: dict) -> Tuple[dict, dict]:
    return (dict(config, rows_per_round=1 << 13, buffer_capacity=3 * 2048,
                 leaf_size=256),
            dict(traffic, batch=2048, readback_rows=64))
