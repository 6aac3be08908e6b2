"""A collection key-range sharded over the chips, one shard a chip,
bulk-loaded from blocks made on the chips and probed through the
one-launch mesh scan: the ``mesh_probe`` loop."""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import blockref
from system import registry_snapshot, summary_config

FALLBACKS = "query.mesh_fallbacks_total"
EXCHANGED = "shard.exchange_bytes_total"


class ShardedCollection:
    """``ShardedCoconutLSM`` as the deployment configures it: its bulk
    load, its mesh pin and its exact batched probes."""

    def __init__(self, config: dict):
        self.config = config
        self.cfg = summary_config(config)

    def load(self, blocks: List[jax.Array]):
        """A fresh engine holding the collection, one block per chip
        (the engine consumes and deletes the blocks)."""
        from repro.distributed.sharded_lsm import ShardedCoconutLSM
        c = self.config
        eng = ShardedCoconutLSM(self.cfg, shards=c["shards"],
                                leaf_size=c["leaf_size"],
                                materialized=c["materialized"],
                                scan_mode=c["scan_mode"])
        eng.bulk_load(blocks)
        return eng

    @staticmethod
    def pin(eng) -> Dict[str, object]:
        """Pin the runs for the mesh scan; facts about the load and the
        pin for the log."""
        pinned = eng.pin_mesh()
        return {"rows_per_shard": eng.shard_sizes(),
                "exchange_bytes": registry_snapshot().get(EXCHANGED, 0),
                "cap": None if pinned is None else pinned.layout.cap,
                "mesh_devices": None if pinned is None
                else pinned.layout.n_devices}

    @staticmethod
    def search(eng, queries: np.ndarray, *, k: int
               ) -> Tuple[np.ndarray, np.ndarray, Dict[str, int]]:
        """Exact k-NN of a query batch.  Returns (squared distances
        ``[Q, k]``, global ids ``[Q, k]``, counts): the real rows the
        launch scanned (``rows_pinned``), the most rows one query of the
        batch verified (``rows_verified``, at least the rows whose series
        the launch read), and ``mesh_fallbacks``, 1 where the call was
        not answered by the mesh launch."""
        before = registry_snapshot().get(FALLBACKS, 0)
        d, ids, info = eng.search_exact_batch(queries, k=k)
        fell = registry_snapshot().get(FALLBACKS, 0) - before
        mesh = info.get("scan_mode") == "mesh"
        per_q = info["candidates_per_query"]
        counts = {"rows_pinned": int(info.get("mesh_rows", 0)),
                  "rows_verified": (int(per_q.max()) - info["buffer_rows"]
                                    if len(per_q) else 0),
                  "mesh_fallbacks": int(fell or not mesh)}
        return (np.asarray(d, np.float32), np.asarray(ids, np.int64),
                counts)

    @staticmethod
    def close(eng) -> None:
        eng.close()


class BruteBlocksBf16(ShardedCollection):
    """The reference in bfloat16 in the program's place: each block
    scanned whole on its own chip (``refknn.brute_topk``), the chips'
    top k merged by (distance, id)."""

    def load(self, blocks):
        return list(blocks)

    @staticmethod
    def pin(blocks):
        return {"rows_per_shard": [int(b.shape[0]) for b in blocks]}

    @staticmethod
    def search(blocks, queries, *, k):
        d, i = blockref.blocks_topk(blocks, queries, k=k,
                                    dtype=jnp.bfloat16)
        return d, i, {"rows_pinned": 0, "rows_verified": 0,
                      "mesh_fallbacks": 0}

    @staticmethod
    def close(blocks) -> None:
        blocks.clear()


def system(config: dict) -> ShardedCollection:
    return ShardedCollection(config)


def control(config: dict, traffic: dict) -> ShardedCollection:
    return BruteBlocksBf16(config)


def cpu_size(config: dict, traffic: dict) -> Tuple[dict, dict]:
    return (dict(config, series=1 << 13, leaf_size=256, make_block=2048),
            dict(traffic))
