"""Repeated bulk loads of a device-resident collection."""
from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

import refknn
import seriesgen
from loops import _CHECK, _DATA, Loop, _limits, _no_annotation


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


LOOP = BuildLoop
