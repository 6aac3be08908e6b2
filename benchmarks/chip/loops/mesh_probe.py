"""Closed loop of exact k-NN probes of a collection made as one block
on each chip and bulk-loaded into a sharded engine."""
from __future__ import annotations

from typing import Dict, Iterator, List

import jax
import numpy as np

import blockref
import refknn
import seriesgen
from chipenv import key_from_seed
from loops import (_DATA, _ORDER, _POOL, _WARM, Loop, _limits,
                   _no_annotation)


class MeshProbeLoop(Loop):
    """Exact k-NN probes of a collection spread over the chips, cycling
    a query pool.

    The collection is one block of ``series / chips`` rows per chip
    (``chips``: the devices JAX reports, at most ``shards``), block
    ``j`` made on chip ``j`` from ``fold_in(fold_in(data, _DATA), j)``;
    ``data`` is the mix's fixed ``data_key``, so every seed probes the
    same collection with the same queries, in an order of its own.  The
    blocks go to the system's ``load``, which consumes them; the check
    remakes them.  Traffic keys: ``queries_per_call``, ``k``,
    ``pool_calls`` (calls in one pass of the pool, an equal share of
    its queries from each chip's block), ``noise``, ``warmup_calls``,
    ``data_key``.  The window ends on a whole pass of the pool."""

    kind = "probe"

    def _phase(self, name: str) -> None:
        """End a set-up step, keeping each chip's bytes in use and peak
        bytes at its end (absent where the backend keeps no count)."""
        super()._phase(name)
        stats = [d.memory_stats() or {} for d in self._devices()]
        self.memory[name] = {
            k: [m.get(stat) for m in stats]
            for k, stat in (("in_use", "bytes_in_use"),
                            ("peak", "peak_bytes_in_use"))}

    def _devices(self) -> list:
        return jax.devices()[:self.config["shards"]]

    def _blocks(self) -> Iterator[jax.Array]:
        """The collection's blocks, each made on its own chip."""
        c = self.config
        devs = self._devices()
        data = jax.random.fold_in(key_from_seed(self.traffic["data_key"]),
                                  _DATA)
        for j, dev in enumerate(devs):
            with jax.default_device(dev):
                block = seriesgen.random_walk_blocks(
                    jax.random.fold_in(data, j), c["series"] // len(devs),
                    c["series_len"], c["make_block"])
            # committed (no copy): what reads it runs on its chip
            yield jax.device_put(block, dev)

    def _members(self, blocks: List[jax.Array], which: int,
                 n: int) -> np.ndarray:
        """``n`` noisy-member queries, an equal share from each block."""
        data = jax.random.fold_in(key_from_seed(self.traffic["data_key"]),
                                  which)
        share = n // len(blocks)
        return np.concatenate([np.asarray(seriesgen.noisy_members(
            jax.random.fold_in(data, j), b, share, self.traffic["noise"]))
            for j, b in enumerate(blocks)])

    def setup(self) -> None:
        t = self.traffic
        q = t["queries_per_call"]
        self.memory: Dict[str, dict] = {}
        blocks = list(self._blocks())
        pool = self._members(blocks, _POOL, t["pool_calls"] * q)
        warm = self._members(blocks, _WARM, t["warmup_calls"] * q)
        order = np.asarray(jax.random.permutation(
            jax.random.fold_in(self.key, _ORDER), len(pool)))
        self.pool = pool[order].reshape(t["pool_calls"], q, -1)
        warm = warm.reshape(t["warmup_calls"], q, -1)
        self._phase("data_s")
        self.state = self.system.load(blocks)
        del blocks                    # the system holds what it keeps
        self._phase("load_s")
        self.facts = self.system.pin(self.state)
        self._phase("pin_s")
        for w in warm:
            self.system.search(self.state, w, k=t["k"])
        self._phase("warmup_s")

    def window(self, seconds: float, annotate=_no_annotation) -> None:
        npool = len(self.pool)

        def call(i: int) -> dict:
            p = i % npool
            d, ids, counts = self.system.search(
                self.state, self.pool[p], k=self.traffic["k"])
            return {"pool": p, "queries": len(self.pool[p]), "d": d,
                    "ids": ids, "counts": counts}

        self._run_window(seconds, call, annotate,
                         done=lambda: len(self.calls) % npool == 0)

    def release(self) -> None:
        if self.state is not None:
            self.system.close(self.state)
        self.state = None
        super().release()

    def check(self) -> dict:
        t = self.traffic
        ok = [c for c in self.calls if not c.get("error")]
        used = sorted({c["pool"] for c in ok})
        q, L = self.pool.shape[1:]
        ref_d, ref_i = blockref.blocks_topk(
            self._blocks(), self.pool[used].reshape(-1, L), k=t["k"])
        row = {p: j for j, p in enumerate(used)}
        ref_d = ref_d.reshape(len(used), q, t["k"])
        ref_i = ref_i.reshape(len(used), q, t["k"])
        wrong, worst, fell = 0, 0, 0
        for c in ok:
            j = row[c["pool"]]
            wrong += int((c["ids"] != ref_i[j]).sum())
            worst = max(worst, refknn.max_ulp(c["d"], ref_d[j]))
            fell += c["counts"]["mesh_fallbacks"]
        return {"checks": _limits(t, {"ids_wrong": wrong,
                                      "dist_ulp": worst,
                                      "mesh_fallbacks": fell}),
                "extra": {}}

    def info(self, counters: Dict[str, float]) -> dict:
        return {"chips": len(self._devices()), "memory": self.memory,
                **self.facts}


LOOP = MeshProbeLoop
