"""Distributed sample-sort under shard_map — the paper's external sort at
pod scale.

The paper bulk-loads by external sort (partition -> merge, Sec. 3.1).  On a
TPU pod the equivalent is a sample-sort over the ``data`` axis:

  1. local sort of each shard's keys (on-device lexsort),
  2. splitter selection from a regular sample of each shard (all-gathered,
     tiny), giving d-1 global splitters,
  3. ``all_to_all`` exchange routing each element to its range partition,
  4. local merge (sort) of the received buckets.

One collective round instead of the paper's log-passes of disk merging; the
output is globally range-partitioned and locally sorted — exactly the
layout the sharded Coconut-Tree needs (paper Sec. 7 names parallel UB-tree
building as future work; this realizes it).

Because shard buckets are unequal, routing pads each bucket to the uniform
per-destination capacity ``cap`` with +inf keys and sorts them to the tail;
``counts`` reports real sizes.  Capacity overflow raises at the caller's
chosen safety factor (2x by default — random keys concentrate tightly).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import keys as K

__all__ = ["sharded_sort", "splitters_from_sample", "local_topk_merge"]


def splitters_from_sample(keys: np.ndarray, d: int) -> np.ndarray:
    """Select ``d-1`` range splitters from a key sample — the host-side
    twin of the splitter step inside :func:`sharded_sort` (sort the
    sample, take every ``len/d``-th key).

    ``keys``: ``[M, n_words]`` uint32 z-order keys (any order).
    Returns ``[d-1, n_words]`` ascending splitter keys.  The sharded
    streaming router uses this to estimate (and re-estimate) its shard
    boundaries from sampled insert keys, so the static bulk-load and the
    streaming engine partition the keyspace the same way.
    """
    keys = np.asarray(keys, np.uint32)
    if d < 2:
        return np.zeros((0, keys.shape[1]), np.uint32)
    s = keys[K.lexsort_keys_np(keys)]
    pos = (np.arange(1, d) * len(s)) // d
    return np.ascontiguousarray(s[np.minimum(pos, len(s) - 1)])


def sharded_sort(mesh, keys: jax.Array, payload: jax.Array, *,
                 axis: str = "data", cap_factor: float = 2.0
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Globally sort (keys, payload) rows across mesh axis ``axis``.

    keys: [N, n_words] uint32 (z-order keys), sharded on dim 0 over ``axis``.
    payload: [N, ...] rows carried with their keys (offsets or raw series).

    Returns (sorted_keys, sorted_payload, valid_counts) where each shard
    holds its range partition padded to ``cap = cap_factor * N/d`` rows;
    ``valid_counts`` [d] gives real rows per shard.  Rows beyond the count
    are +inf-key padding.
    """
    d = mesh.shape[axis]
    n_words = keys.shape[1]
    pay_shape = payload.shape[1:]

    def body(k_loc, p_loc):
        n_loc = k_loc.shape[0]
        if d == 1:                  # degenerate mesh: plain local sort
            order = K.lexsort_keys(k_loc)
            return (k_loc[order], p_loc[order],
                    jnp.full((1,), n_loc, jnp.int32))
        cap = int(cap_factor * n_loc)
        my = jax.lax.axis_index(axis)

        # 1. local sort
        order = K.lexsort_keys(k_loc)
        k_loc = k_loc[order]
        p_loc = p_loc[order]

        # 2. splitters: sample d evenly spaced keys per shard, all-gather,
        #    take every d-th of the merged sorted sample
        step = max(n_loc // d, 1)
        sample = k_loc[:: step][:d]                       # [d, w]
        all_samples = jax.lax.all_gather(sample, axis)    # [d, d, w]
        flat = all_samples.reshape(d * d, n_words)
        so = K.lexsort_keys(flat)
        flat = flat[so]
        splitters = flat[d:: d][: d - 1]                  # [d-1, w]

        # 3. destination shard per row = searchsorted over splitters
        dest = K.searchsorted_keys(splitters, k_loc, side="right")  # [n]

        # bucketize into [d, cap] with padding
        one_hot = dest[:, None] == jnp.arange(d)[None, :]
        pos_in_dest = jnp.cumsum(one_hot, axis=0) - 1     # rank within bucket
        slot = jnp.sum(pos_in_dest * one_hot, axis=1)
        overflow = slot >= cap
        sink = d * cap
        flat_pos = jnp.where(overflow, sink, dest * cap + slot)

        pad_keys = jnp.full((d * cap + 1, n_words), jnp.uint32(0xFFFFFFFF))
        pad_pay = jnp.zeros((d * cap + 1,) + pay_shape, payload.dtype)
        bk = pad_keys.at[flat_pos].set(k_loc)[: d * cap] \
            .reshape(d, cap, n_words)
        bp = pad_pay.at[flat_pos].set(p_loc)[: d * cap] \
            .reshape((d, cap) + pay_shape)

        # 4. all_to_all: shard i sends bucket j to shard j
        rk = jax.lax.all_to_all(bk, axis, split_axis=0, concat_axis=0,
                                tiled=False)
        rp = jax.lax.all_to_all(bp, axis, split_axis=0, concat_axis=0,
                                tiled=False)
        rk = rk.reshape(d * cap, n_words)
        rp = rp.reshape((d * cap,) + pay_shape)

        # 5. local merge: padding keys (all-0xFF) sort to the tail
        o2 = K.lexsort_keys(rk)
        rk = rk[o2]
        rp = rp[o2]
        valid = jnp.sum(~jnp.all(rk == jnp.uint32(0xFFFFFFFF), axis=1))
        had_overflow = jnp.any(overflow)
        valid = jnp.where(had_overflow, -valid - 1, valid)  # signal overflow
        return rk, rp, valid[None].astype(jnp.int32)

    from jax.sharding import PartitionSpec as P
    in_specs = (P(axis, None), P(axis) if payload.ndim == 1
                else P(axis, *([None] * (payload.ndim - 1))))
    out_specs = (P(axis, None),
                 P(axis) if payload.ndim == 1
                 else P(axis, *([None] * (payload.ndim - 1))),
                 P(axis))
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    rk, rp, counts = fn(keys, payload)
    return rk, rp, counts


def local_topk_merge(mesh, dists: jax.Array, ids: jax.Array, k: int,
                     axis: str = "data") -> Tuple[jax.Array, jax.Array]:
    """Merge per-shard candidate (dist, id) lists into a global top-k.

    dists/ids: [N] sharded over ``axis``; returns replicated [k] arrays —
    the collective tail of the distributed SIMS exact search.
    """

    def body(d_loc, i_loc):
        neg, idx = jax.lax.top_k(-d_loc, min(k, d_loc.shape[0]))
        d_top, i_top = -neg, i_loc[idx]
        d_all = jax.lax.all_gather(d_top, axis).reshape(-1)
        i_all = jax.lax.all_gather(i_top, axis).reshape(-1)
        neg2, idx2 = jax.lax.top_k(-d_all, k)
        return -neg2, i_all[idx2]

    from jax.sharding import PartitionSpec as P
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis)),
                           out_specs=(P(), P()), check_vma=False)
    return fn(dists, ids)
