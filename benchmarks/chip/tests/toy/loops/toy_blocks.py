"""A toy loop: the collection made in blocks, one on each device JAX
reports, and closed-loop exact k-NN calls over all of them."""
from __future__ import annotations

import jax
import numpy as np

import refknn
import seriesgen
from chipenv import key_from_seed
from loops import _DATA, _POOL, Loop, _limits, _no_annotation


class BlocksLoop(Loop):
    """Traffic keys: ``queries_per_call``, ``k``, ``pool_calls``,
    ``noise``, ``data_key``."""

    def setup(self) -> None:
        c, t = self.config, self.traffic
        devs = jax.devices()
        rows = c["series"] // len(devs)
        data = key_from_seed(t["data_key"])
        self.blocks = []
        for j, dev in enumerate(devs):
            with jax.default_device(dev):
                self.blocks.append(seriesgen.random_walk(
                    jax.random.fold_in(jax.random.fold_in(data, _DATA), j),
                    rows, c["series_len"]))
        q = t["queries_per_call"]
        pool = seriesgen.noisy_members(
            jax.random.fold_in(self.key, _POOL), self.blocks[0],
            t["pool_calls"] * q, t["noise"])
        self.pool = np.asarray(pool).reshape(t["pool_calls"], q, -1)
        self._phase("data_s")
        self.system.search(self.blocks, self.pool[0], k=t["k"])
        self._phase("warmup_s")

    def window(self, seconds: float, annotate=_no_annotation) -> None:
        def call(i: int) -> dict:
            p = i % len(self.pool)
            d, ids = self.system.search(self.blocks, self.pool[p],
                                        k=self.traffic["k"])
            return {"pool": p, "queries": len(self.pool[p]), "d": d,
                    "ids": ids}

        self._run_window(seconds, call, annotate)

    def check(self) -> dict:
        rows = jax.device_put(np.concatenate(
            [np.asarray(b) for b in self.blocks]))
        ok = [c for c in self.calls if not c.get("error")]
        ref_d, ref_i = refknn.brute_topk_blocked(
            rows, np.concatenate([self.pool[c["pool"]] for c in ok]),
            k=self.traffic["k"])
        d = np.concatenate([c["d"] for c in ok])
        ids = np.concatenate([c["ids"] for c in ok])
        out = {"ids_wrong": int((ids != ref_i).sum()),
               "dist_ulp": refknn.max_ulp(d, ref_d)}
        return {"checks": _limits(self.traffic, out), "extra": {}}

    def info(self, counters) -> dict:
        return {"block_devices": [str(b.devices().pop())
                                  for b in self.blocks]}


LOOP = BlocksLoop
