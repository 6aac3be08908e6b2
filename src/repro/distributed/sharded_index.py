"""Range-partitioned Coconut-Tree across the ``data`` mesh axis + the
distributed SIMS exact search.

The paper names parallelization as future work (Sec. 7).  This module
realizes it:

  * **bulk-load**: distributed sample-sort (one ``all_to_all`` round)
    range-partitions the z-order keyspace across shards; each shard then IS
    a local Coconut-Tree over its contiguous key range — contiguity, the
    paper's central property, is preserved *across* devices.
  * **query**: the query is broadcast; every shard scans its in-memory
    summarizations with the mindist lower bound (the Pallas hot loop),
    verifies its own unpruned candidates, and a tiny per-shard top-k is
    all-gathered and reduced — one collective of O(k) per query.

Everything is expressed with shard_map + jax.lax collectives so the same
code lowers to the 512-chip production mesh in the dry-run.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import keys as K
from ..core import summarization as S
from ..kernels import mesh_scan as _mesh
from .samplesort import sharded_sort

__all__ = ["ShardedCoconutTree", "build_sharded", "distributed_exact_search",
           "distributed_exact_search_batch"]


@dataclasses.dataclass
class ShardedCoconutTree:
    """Device-sharded sorted index: shard i owns keyspace range i."""
    keys: jax.Array        # [d*cap, n_words] uint32, dim0 sharded over axis
    codes: jax.Array       # [d*cap, w] uint8
    paas: jax.Array        # [d*cap, w] f32
    raw: jax.Array         # [d*cap, L] f32 (materialized, co-partitioned)
    counts: jax.Array      # [d] valid rows per shard
    cfg: S.SummaryConfig
    mesh: object
    axis: str = "data"
    ts: Optional[jax.Array] = None   # [d*cap] f32 timestamps (co-routed)

    @property
    def n_valid(self) -> int:
        return int(jnp.sum(jnp.abs(self.counts)))


def build_sharded(mesh, raw: jax.Array, cfg: S.SummaryConfig, *,
                  axis: str = "data",
                  cap_factor: float = 2.0,
                  timestamps: Optional[jax.Array] = None
                  ) -> ShardedCoconutTree:
    """Distributed bulk-load: summarize locally, sample-sort globally.

    ``raw``: [N, L] float32 with N divisible by the axis size; arrives
    sharded (or is resharded) over ``axis``.  ``timestamps`` (optional
    [N] ints) are co-routed with their rows so window queries
    (``ts_min``) filter on-shard; they ride the f32 payload, exact for
    values < 2**24.
    """
    d = mesh.shape[axis]
    n, L = raw.shape
    assert n % d == 0, f"N={n} must divide over {axis}={d}"
    # summarize before placing: the summaries and keys are row-wise, and
    # jnp.searchsorted's scan is rejected on explicitly sharded inputs
    raw = jnp.asarray(raw, jnp.float32)
    paas, codes = S.summarize(raw, cfg)
    keys = S.invsax_keys(codes, cfg)
    # payload rows: raw co-sorted with keys (materialized index) + the PAA /
    # codes needed by the SIMS scan (+ optional ts), one f32 payload matrix
    cols = [raw, paas, codes.astype(jnp.float32)]
    if timestamps is not None:
        cols.append(jnp.asarray(timestamps, jnp.float32)[:, None])
    pay = jnp.concatenate(cols, axis=1)
    sh = NamedSharding(mesh, P(axis, None))
    skeys, spay, counts = sharded_sort(mesh, jax.device_put(keys, sh),
                                       jax.device_put(pay, sh), axis=axis,
                                       cap_factor=cap_factor)
    if bool(jnp.any(counts < 0)):
        raise RuntimeError("sample-sort bucket overflow; raise cap_factor")
    w = cfg.segments
    return ShardedCoconutTree(
        keys=skeys,
        raw=spay[:, :L],
        paas=spay[:, L: L + w],
        codes=spay[:, L + w: L + 2 * w].astype(jnp.uint8),
        ts=spay[:, L + 2 * w] if timestamps is not None else None,
        counts=counts, cfg=cfg, mesh=mesh, axis=axis)


def distributed_exact_search_batch(tree: ShardedCoconutTree,
                                   queries: jax.Array, k: int = 1, *,
                                   budget: Optional[int] = None,
                                   ts_min: Optional[int] = None):
    """Batched exact k-NN: broadcast the query batch, per-shard ``[Q, k]``
    partials, ONE all-gather for the whole batch — the single shard-map
    body every distributed search entry point funnels through.

    queries ``[Q, L]`` -> (dists_sq ``[Q, k]``, rows ``[Q, k, L]``).  Each
    shard runs the batched mindist scan over its local summaries (one code
    pass serves all Q queries) and verifies its own candidates; the
    collective cost is O(Q*k) per batch instead of O(k) per query — the
    distributed arm of the batched search engine.  Row qi with k=1 equals
    ``distributed_exact_search(tree, queries[qi])``.

    ``ts_min``: restrict to rows with timestamp >= ts_min (window
    filtering; requires ``build_sharded(..., timestamps=...)``).
    ``budget``: verify only the ``budget`` best lower bounds per shard
    (the skip-sequential discipline of SIMS, fixed-shape for jit); the
    return grows a third element ``certified [Q]`` — True iff the
    query's answer is provably exact under the budget.
    """
    cfg = tree.cfg
    q = jnp.atleast_2d(jnp.asarray(queries, jnp.float32))   # [Q, L]
    q_paas = S.paa(q, cfg.segments)                         # [Q, w]
    axis = tree.axis
    nq = q.shape[0]
    if ts_min is not None and tree.ts is None:
        raise ValueError("ts_min needs a tree built with timestamps")
    ts = tree.ts if tree.ts is not None else jnp.zeros(
        tree.keys.shape[0], jnp.float32)

    scale = cfg.series_len / cfg.segments
    env_lower, env_upper = _mesh._finite_bounds(cfg.bits)

    def body(codes, paas, raw, keys, ts_loc):
        valid = ~jnp.all(keys == jnp.uint32(0xFFFFFFFF), axis=1)
        if ts_min is not None:
            valid = valid & (ts_loc >= jnp.float32(ts_min))
        if budget is None:
            # verify ALL unpruned rows through the shared device-scan
            # helper (the mesh launch's per-device body): with bound
            # +inf every valid row stays live — md <= ed always — so
            # this is the same masked-ED top-k, one formulation shared
            # with the sharded-LSM mesh path
            dead = (~valid).astype(jnp.int32)
            cand_d, idx, _live = _mesh.local_scan_topk(
                q, q_paas, codes, raw, dead,
                jnp.full(nq, jnp.inf, jnp.float32),
                env_lower, env_upper, scale=scale, k=k)
            cand_rows = raw[jnp.maximum(idx, 0)]             # [Q, k, L]
            certified = jnp.ones(nq, bool)
            diffk = cand_rows - q[:, None, :]
            # final bits from the one [Q, k, L] recompute both branches
            # share — the scan above only SELECTS the candidates, so
            # budget/no-budget answers stay bit-identical
            cand_d = jnp.where(jnp.isfinite(cand_d), S.sum_sq(diffk),
                               jnp.inf)
        else:
            # ONE local lower-bound pass for the whole batch (batched
            # kernel op shape), amortizing the code stream across all Q
            md = S.mindist_sq_batch(q_paas, codes, cfg)      # [Q, n_loc]
            md = jnp.where(valid[None, :], md, jnp.inf)
            # verify only the budget best lower bounds per query
            negm, order = jax.lax.top_k(-md, budget)         # [Q, budget]
            rows = raw[order]                                # [Q, B, L]
            diff = rows - q[:, None, :]
            ed = S.sum_sq(diff)                              # [Q, B]
            ed = jnp.where(jnp.isfinite(-negm), ed, jnp.inf)
            neg, idx = jax.lax.top_k(-ed, k)                 # [Q, k]
            cand_d = -neg
            cand_rows = jnp.take_along_axis(rows, idx[:, :, None],
                                            axis=1)
            diffk = cand_rows - q[:, None, :]
            cand_d = jnp.where(jnp.isfinite(cand_d), S.sum_sq(diffk),
                               jnp.inf)
            # certified iff the worst verified lower bound exceeds the
            # best found distance (per query, on this shard)
            certified = (-negm[:, budget - 1]) >= cand_d[:, 0]
        d_all = jax.lax.all_gather(cand_d, axis)             # [d, Q, k]
        r_all = jax.lax.all_gather(cand_rows, axis)          # [d, Q, k, L]
        c_all = jax.lax.all_gather(certified, axis)          # [d, Q]
        nd = d_all.shape[0]
        d_all = jnp.transpose(d_all, (1, 0, 2)).reshape(nq, nd * k)
        r_all = jnp.transpose(r_all, (1, 0, 2, 3)).reshape(
            nq, nd * k, raw.shape[1])
        neg2, idx2 = jax.lax.top_k(-d_all, k)                # [Q, k]
        rows_out = jnp.take_along_axis(r_all, idx2[:, :, None], axis=1)
        return -neg2, rows_out, jnp.all(c_all, axis=0)

    fn = jax.shard_map(
        body, mesh=tree.mesh,
        in_specs=(P(axis, None),) * 4 + (P(axis),),
        out_specs=(P(None, None), P(None, None, None), P(None,)),
        check_vma=False)
    d, rows, cert = fn(tree.codes, tree.paas, tree.raw, tree.keys, ts)
    if budget is None:
        return d, rows
    return d, rows, cert


def distributed_exact_search(tree: ShardedCoconutTree, query: jax.Array,
                             k: int = 1, *,
                             ts_min: Optional[int] = None
                             ) -> Tuple[jax.Array, jax.Array]:
    """Exact k-NN for one query — Q=1 wrapper over
    :func:`distributed_exact_search_batch` (one body, one collective).

    Returns (dists_sq [k], row_payloads [k, L]) — the k nearest raw series.
    """
    d, rows = distributed_exact_search_batch(
        tree, jnp.asarray(query, jnp.float32)[None, :], k, ts_min=ts_min)
    return d[0], rows[0]


# (the deprecated `distributed_exact_search_pruned` alias is gone —
# call `distributed_exact_search_batch(..., budget=)`, which returns the
# batched (dists [Q, k], rows [Q, k, L], certified [Q]) shape.)
