"""The system under test, as the benchmark drives it.

This is the one module of the benchmark that imports the program
(``src/repro``): its entry points, and its spans and counters.  The
loops call only what is here, so the control and the fault tests can put
something else in the program's place without touching the loops.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import numpy as np


def summary_config(config: dict):
    from repro.core.summarization import SummaryConfig
    return SummaryConfig(series_len=config["series_len"],
                         segments=config["segments"], bits=config["bits"])


def search_counts(stats) -> Dict[str, int]:
    """The counts of one probe that the per-layer readers use."""
    return {"leaves_scanned": int(stats.leaves_scanned),
            "leaves_pruned": int(stats.leaves_pruned),
            "candidates": int(stats.candidates),
            "scan_bytes": int(stats.scan_bytes)}


class StaticIndex:
    """A Coconut-Tree bulk-loaded from a device-resident collection."""

    def __init__(self, config: dict):
        self.config = config
        self.cfg = summary_config(config)

    def build(self, raw: jax.Array):
        """Bulk-load ``raw`` and wait until every array of the tree is
        on the device."""
        from repro.core import tree as T
        tree = T.build(raw, self.cfg, leaf_size=self.config["leaf_size"],
                       materialized=self.config["materialized"])
        return jax.block_until_ready(tree)

    def search(self, tree, queries: np.ndarray, *, k: int,
               budget: Optional[int]
               ) -> Tuple[np.ndarray, np.ndarray, Dict[str, int], object]:
        """k-NN of a query batch: exact, or best-first under a leaf
        budget.  Returns (squared distances ``[Q, k]``, row indices of
        the collection ``[Q, k]``, counts, certified gap ``[Q]`` or
        None)."""
        from repro.core import tree as T
        d, ids, st = T.exact_search_batch(tree, queries, k=k,
                                          budget=budget)
        return (np.asarray(d, np.float32), np.asarray(ids, np.int64),
                search_counts(st), None if st.gap is None
                else np.asarray(st.gap, np.float32))

    @staticmethod
    def layout(tree) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """(z-order keys, co-sorted raw rows, row index of each) of a
        built tree, as the check reads them."""
        return tree.keys, tree.raw, tree.offsets


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


# ---------------------------------------------------------- observability
def enable_spans(capacity: int = 1 << 21) -> None:
    from repro.obs import enable_tracing, get_tracer
    get_tracer().clear()
    enable_tracing(capacity)


def take_spans() -> Tuple[List[dict], float, int]:
    """Finished spans (``ts``/``dur`` in microseconds from the tracer's
    epoch), the epoch on ``time.perf_counter``'s clock, and how many
    spans the ring dropped; stops tracing."""
    from repro.obs import disable_tracing, get_tracer
    tr = get_tracer()
    disable_tracing()
    return tr.spans(), tr.epoch, tr.dropped


def registry_snapshot() -> Dict[str, float]:
    """Counters by name, histograms as ``name.count`` / ``name.sum``."""
    from repro.obs import get_registry
    return get_registry().snapshot()
