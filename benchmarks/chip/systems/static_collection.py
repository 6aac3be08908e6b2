"""A static collection bulk-loaded into one Coconut-Tree on one chip: the
``build``, exact and budgeted ``probe`` loops."""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import refknn
from system import bf16_round, search_counts, summary_config


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


class ProbeControl(StaticIndex):
    """The reference in bfloat16 in the program's place: its "index" is
    the collection itself and every call scans all of it."""

    def build(self, raw):
        return raw

    def search(self, tree, queries, *, k, budget):
        d, ids = refknn.brute_topk_blocked(tree, queries, k=k,
                                           qblock=len(queries),
                                           dtype=jnp.bfloat16)
        counts = {"leaves_scanned": 0, "leaves_pruned": 0,
                  "candidates": 0, "scan_bytes": 0}
        gap = None if budget is None else np.zeros(len(queries),
                                                    np.float32)
        return d, ids, counts, gap


class BuildControl(StaticIndex):
    """The program's bulk load, its co-sorted rows stored rounded to
    bfloat16 (rounding the collection before the build would hold a
    third 4 GiB copy on a chip that has room for two)."""

    def build(self, raw):
        tree = super().build(raw)
        return dataclasses.replace(tree, raw=bf16_round(tree.raw))


def system(config: dict) -> StaticIndex:
    return StaticIndex(config)


def control(config: dict, traffic: dict) -> StaticIndex:
    """The probe loops: the reference scan in bfloat16 answers the
    calls; the build loop: the tree keeps its rows in bfloat16."""
    if traffic["loop"] == "build":
        return BuildControl(config)
    return ProbeControl(config)


def cpu_size(config: dict, traffic: dict) -> Tuple[dict, dict]:
    config = dict(config, series=1 << 13, leaf_size=256, make_block=2048)
    traffic = dict(traffic)
    if traffic["loop"] == "probe":
        traffic.update(pool_calls=min(traffic["pool_calls"], 8),
                       warmup_calls=2)
    return config, traffic
