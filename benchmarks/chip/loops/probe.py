"""Closed loop of k-NN probes of a static collection."""
from __future__ import annotations

from typing import Dict

import jax
import numpy as np

import refknn
import seriesgen
from chipenv import key_from_seed
from loops import (_DATA, _ORDER, _POOL, _WARM, Loop, _limits,
                   _no_annotation)


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


LOOP = ProbeLoop
