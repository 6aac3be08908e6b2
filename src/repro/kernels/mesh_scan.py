"""One-launch device-resident sharded scan (``shard_map`` over a 1-D mesh).

The threaded sharded hot path fans a probe batch out to N per-shard
Python pipelines and merges N host-side pools.  This module replaces
that with ONE compiled program: every shard's immutable columns are
pinned as device-sharded ``[S, cap, ...]`` stacks on a 1-D ``Mesh``, and
a single ``shard_map``-ed body runs per-device mindist prune + masked
Euclidean verify + local top-k, then an ``all_gather`` merge — the
"Data Series Indexing Gone Parallel" intra-node scan, expressed as one
XLA executable.

Parity contract: the per-device compute reuses the exact ``ref.py``
formulas of the fused ``scan_verify`` kernel (the eager threaded chain
computes the same expressions), and the merge only *selects* distance
values — it never re-derives them — so answer bits match the threaded
path on the same backend.  ``ref.mesh_scan_ref`` is the single-device
oracle the launch is tested against.

Any device count: the stacked dim 0 holds S shards but the mesh spans
D = the largest divisor of S that fits the available devices; each
device body flattens its ``spd = S / D`` sub-shards into one local scan.
With one CPU device every shard count degenerates to D=1 and the launch
still runs (that is how the parity suite executes without
``--xla_force_host_platform_device_count``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import summarization as S
from . import ref
from .scan_verify import scan_verify_pallas

__all__ = ["local_scan_topk", "mesh_scan_launch"]

# finite sentinels for the region-bound tables (same values ops.py uses;
# PAA values sit within a few sigma, so 1e30 behaves as +/-inf and the
# mindist bits are identical to the inf-ended tables)
_NEG, _POS = -1e30, 1e30


def _finite_bounds(bits: int):
    lower, upper = S.region_bounds(bits)
    return (jnp.nan_to_num(lower, neginf=_NEG),
            jnp.nan_to_num(upper, posinf=_POS))


def local_scan_topk(queries: jax.Array, q_paas: jax.Array,
                    codes: jax.Array, raw: jax.Array, dead: jax.Array,
                    bound: jax.Array, lower: jax.Array, upper: jax.Array,
                    *, scale: float, k: int):
    """One device's fused scan: mindist bound -> bound-masked ED ->
    local top-k.  The traced twin of ``ref.scan_verify_ref`` (same
    formulas, same bits) that additionally returns the live mask so
    callers can attribute verified counts per sub-shard.

    queries [Q, L], q_paas [Q, w], codes [N, w], raw [N, L], dead [N]
    int32 (nonzero = invisible), bound [Q] strict best-so-far.
    Returns (d [Q, k] inf-padded, idx [Q, k] int32 with -1 padding,
    live [Q, N] bool).
    """
    md = ref.mindist_batch_ref(q_paas, codes, lower, upper, scale)
    live = (md < bound[:, None]) & (dead[None, :] == 0)
    # blocked ED: fixed-shape reduction body, so the bits are invariant
    # to the local row count (any shard/device split of the same rows)
    ed = jnp.where(live, ref.batch_euclid_blocked_ref(queries, raw),
                   jnp.inf)
    neg, idx = jax.lax.top_k(-ed, k)
    d = -neg
    idx = jnp.where(jnp.isfinite(d), idx.astype(jnp.int32), -1)
    return d, idx, live


@functools.lru_cache(maxsize=64)
def _build_launch(mesh, axis: str, cfg: S.SummaryConfig, k: int,
                  ts_filter: bool, mode: str):
    scale = cfg.series_len / cfg.segments
    lower, upper = _finite_bounds(cfg.bits)

    # named for the program the device trace shows: ``jit_mesh_scan``
    def mesh_scan(codes, raw, ids, ts, ts_min, queries, q_paas, bound):
        # per-device block: codes [spd, cap, w], raw [spd, cap, L],
        # ids/ts [spd, cap], ts_min [spd]; query inputs replicated
        spd, cap = ids.shape
        dead = ids < 0
        if ts_filter:
            dead = dead | (ts < ts_min[:, None])
        codes_f = codes.reshape(spd * cap, codes.shape[-1])
        raw_f = raw.reshape(spd * cap, raw.shape[-1])
        dead_f = dead.reshape(spd * cap).astype(jnp.int32)
        if mode != "jnp" and spd == 1:
            # single sub-shard per device: the fused Pallas scan_verify
            # kernel IS the per-device body (TPU/GPU serving shape)
            d, idx, counts_q, _union = scan_verify_pallas(
                queries, q_paas, codes_f.astype(jnp.int32), raw_f,
                lower, upper, bound, dead_f, scale=scale, k=k,
                interpret=(mode == "interpret"))
            counts = counts_q[None, :].astype(jnp.int32)
        else:
            d, idx, live = local_scan_topk(
                queries, q_paas, codes_f, raw_f, dead_f, bound,
                lower, upper, scale=scale, k=k)
            counts = jnp.transpose(
                jnp.sum(live.reshape(-1, spd, cap), axis=2)
            ).astype(jnp.int32)
        ids_f = ids.reshape(spd * cap)
        out_ids = jnp.where(idx >= 0, ids_f[jnp.maximum(idx, 0)], -1)
        # merge: gather every device's candidate pool, re-select top-k.
        # Selection only — the distance values flow through unchanged,
        # preserving bit-parity with the single-device oracle.
        d_all = jax.lax.all_gather(d, axis)            # [D, Q, k]
        i_all = jax.lax.all_gather(out_ids, axis)      # [D, Q, k]
        nd, nq = d_all.shape[0], d.shape[0]
        d_all = jnp.transpose(d_all, (1, 0, 2)).reshape(nq, nd * k)
        i_all = jnp.transpose(i_all, (1, 0, 2)).reshape(nq, nd * k)
        neg, sel = jax.lax.top_k(-d_all, k)
        out_d = -neg
        out_i = jnp.take_along_axis(i_all, sel, axis=1)
        out_i = jnp.where(jnp.isfinite(out_d), out_i, -1)
        return out_d, out_i, counts

    fn = jax.shard_map(
        mesh_scan, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None, None),
                  P(axis, None), P(axis, None), P(axis),
                  P(None, None), P(None, None), P(None)),
        out_specs=(P(None, None), P(None, None), P(axis, None)),
        check_vma=False)
    return jax.jit(fn)


def mesh_scan_launch(mesh, axis: str, cfg: S.SummaryConfig, *, k: int,
                     ts_filter: bool, mode: str = "jnp"):
    """The jitted whole-batch launch for (mesh, cfg, k) — cached, so
    repeated probe batches reuse one executable.

    The returned callable takes ``(codes [S, cap, w], raw [S, cap, L],
    ids [S, cap] i32, ts [S, cap] i32, ts_min [S] i32, queries [Q, L],
    q_paas [Q, w], bound [Q])`` with the stacked arrays sharded over
    ``axis`` (S must be divisible by the mesh size) and returns
    ``(dists [Q, k], ids [Q, k] i32, counts [S, Q] i32)`` fully
    replicated/reassembled on host fetch.
    """
    return _build_launch(mesh, axis, cfg, int(k), bool(ts_filter),
                         str(mode))
