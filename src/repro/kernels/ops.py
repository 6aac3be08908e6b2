"""Public, backend-dispatching wrappers for the Coconut kernels.

Dispatch policy (``mode``):
  * ``"auto"``      — Pallas compiled on accelerators (TPU and GPU),
                      pure-jnp reference elsewhere; the
                      ``COCONUT_KERNEL_MODE`` env var overrides the
                      auto choice (force/disable Pallas without code
                      changes — explicit ``mode=`` arguments still win).
  * ``"pallas"``    — Pallas compiled (accelerator only).
  * ``"interpret"`` — Pallas in interpret mode (CPU validation of the TPU
                      kernel body; used by the test suite).
  * ``"jnp"``       — pure-jnp oracle.

These are the entry points the index code uses; `core/` never imports
pallas directly.
"""
from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import summarization as S
from . import ref

# jit-compiled oracle paths: eager dispatch dominated the scan cost
# (123 ms -> 3.3 ms for 200k x 16 codes; §Perf Coconut iteration 1)
_mindist_jit = jax.jit(ref.mindist_ref, static_argnames=("scale",))
_mindist_batch_jit = jax.jit(ref.mindist_batch_ref,
                             static_argnames=("scale",))
_sax_jit = jax.jit(ref.sax_summarize_ref, static_argnames=("segments",))
_euclid_jit = jax.jit(ref.batch_euclid_ref)
_euclid_multi_jit = jax.jit(ref.batch_euclid_multi_ref)
_scan_verify_jit = jax.jit(ref.scan_verify_ref,
                           static_argnames=("scale", "k"))
_mindist_batch_packed_jit = jax.jit(
    ref.mindist_batch_packed_ref,
    static_argnames=("scale", "w", "b"))
from . import mesh_scan as _mesh
from .batch_euclid import batch_euclid_pallas
from .mindist_batch import mindist_batch_pallas
from .mindist_scan import mindist_pallas
from .sax_summarize import sax_summarize_pallas
from .scan_verify import scan_verify_pallas
from .unpack_mindist import unpack_mindist_batch_pallas
from .zorder import zorder_pallas

__all__ = ["mindist", "mindist_batch", "mindist_batch_packed",
           "sax_summarize", "zorder",
           "batch_euclid", "batch_euclid_multi", "scan_verify",
           "mesh_scan", "summarize_and_key"]

# large finite sentinels: TPU tables prefer finite values; any PAA value is
# within a few sigma, so 1e30 behaves as +/-inf in the bound arithmetic.
_NEG, _POS = -1e30, 1e30


_VALID_MODES = ("pallas", "interpret", "jnp")


def _default_mode() -> str:
    """What ``mode="auto"`` resolves to: the ``COCONUT_KERNEL_MODE`` env
    override when set (and valid), else Pallas on TPU/GPU, jnp on CPU."""
    env = os.environ.get("COCONUT_KERNEL_MODE", "").strip().lower()
    if env in _VALID_MODES:
        return env
    return ("pallas" if jax.default_backend() in ("tpu", "gpu")
            else "jnp")


def _resolve(mode: str) -> str:
    if mode != "auto":
        return mode
    return _default_mode()


def _finite_bounds(bits: int) -> Tuple[jax.Array, jax.Array]:
    lower, upper = S.region_bounds(bits)
    lower = jnp.nan_to_num(lower, neginf=_NEG)
    upper = jnp.nan_to_num(upper, posinf=_POS)
    return lower, upper


def mindist(q_paa: jax.Array, codes: jax.Array, cfg: S.SummaryConfig,
            mode: str = "auto") -> jax.Array:
    """Squared iSAX lower bound for all codes: ``[N, w] -> [N]``."""
    mode = _resolve(mode)
    scale = cfg.series_len / cfg.segments
    lower, upper = _finite_bounds(cfg.bits)
    if mode == "jnp":
        return _mindist_jit(q_paa, codes, lower, upper, scale=scale)
    return mindist_pallas(q_paa, codes.astype(jnp.int32), lower, upper,
                          scale=scale, interpret=(mode == "interpret"))


def mindist_batch(q_paas: jax.Array, codes: jax.Array, cfg: S.SummaryConfig,
                  mode: str = "auto") -> jax.Array:
    """Batched squared iSAX lower bound: ``[Q, w] x [N, w] -> [Q, N]``.

    One streaming pass over the codes serves the whole query batch — the
    throughput lever behind ``exact_search_batch``.
    """
    mode = _resolve(mode)
    scale = cfg.series_len / cfg.segments
    lower, upper = _finite_bounds(cfg.bits)
    if mode == "jnp":
        return _mindist_batch_jit(q_paas, codes, lower, upper, scale=scale)
    return mindist_batch_pallas(q_paas, codes.astype(jnp.int32),
                                lower, upper, scale=scale,
                                interpret=(mode == "interpret"))


def mindist_batch_packed(q_paas: jax.Array, packed: jax.Array,
                         cfg: S.SummaryConfig,
                         mode: str = "auto") -> jax.Array:
    """Batched lower bound over v3 *packed* code rows:
    ``[Q, w] x [N, ceil(w*b/8)] -> [Q, N]``.

    The packed-column twin of :func:`mindist_batch` — fused bit-unpack +
    one-hot mindist, so the executor scans cached/device-resident packed
    blocks without a host-side decode round trip.  Both paths compute
    the identical bound (the unpack is exact), so answers never depend
    on which one ran.
    """
    mode = _resolve(mode)
    scale = cfg.series_len / cfg.segments
    lower, upper = _finite_bounds(cfg.bits)
    if mode == "jnp":
        return _mindist_batch_packed_jit(
            q_paas, packed, lower, upper, scale=scale,
            w=cfg.segments, b=cfg.bits)
    return unpack_mindist_batch_pallas(
        q_paas, packed, lower, upper, w=cfg.segments, b=cfg.bits,
        scale=scale, interpret=(mode == "interpret"))


def sax_summarize(x: jax.Array, cfg: S.SummaryConfig, mode: str = "auto"):
    """Raw ``[N, L]`` -> (paa f32 ``[N, w]``, codes int32 ``[N, w]``)."""
    mode = _resolve(mode)
    bps = S.breakpoints(cfg.bits)
    if mode == "jnp":
        return _sax_jit(x, bps, segments=cfg.segments)
    return sax_summarize_pallas(x, bps, segments=cfg.segments,
                                interpret=(mode == "interpret"))


def zorder(codes: jax.Array, cfg: S.SummaryConfig,
           mode: str = "auto") -> jax.Array:
    """SAX codes -> z-order keys ``[N, n_words]`` uint32."""
    mode = _resolve(mode)
    if mode == "jnp":
        return ref.zorder_ref(codes, w=cfg.segments, b=cfg.bits)
    return zorder_pallas(codes, w=cfg.segments, b=cfg.bits,
                         interpret=(mode == "interpret"))


def batch_euclid(query: jax.Array, series: jax.Array,
                 mode: str = "auto") -> jax.Array:
    """query ``[L]``, series ``[N, L]`` -> squared ED ``[N]``."""
    mode = _resolve(mode)
    if mode == "jnp":
        return _euclid_jit(query, series)
    return batch_euclid_pallas(query, series,
                               interpret=(mode == "interpret"))


def batch_euclid_multi(queries: jax.Array, series: jax.Array,
                       mode: str = "auto") -> jax.Array:
    """queries ``[Q, L]``, series ``[N, L]`` -> squared ED ``[Q, N]``.

    No dedicated Pallas kernel yet: the batched verification is
    compute-light next to the mindist scan, so every mode routes to the
    jit'd jnp path (the single-query Pallas kernel remains for 1-NN).
    """
    del mode
    return _euclid_multi_jit(queries, series)


def scan_verify(queries: jax.Array, q_paas: jax.Array, codes: jax.Array,
                raw: jax.Array, bound: jax.Array, cfg: S.SummaryConfig,
                *, k: int = 1, mode: str = "auto",
                dead: jax.Array = None):
    """Fused SIMS scan+verify: one pass computing the iSAX lower bound,
    the bound-masked (early-abandoning) Euclidean verification, and the
    per-query top-k on device.

    queries ``[Q, L]``, q_paas ``[Q, w]``, codes ``[B, w]``, raw
    ``[B, L]``, bound ``[Q]`` per-query best-so-far, ``dead`` optional
    ``[B]`` row filter (nonzero = excluded, e.g. window cuts).  Returns
    (dists ``[Q, k]`` inf-padded, row indices ``[Q, k]`` int32 with -1
    padding, verified counts ``[Q]`` int32, union-verified rows int32 —
    rows live for ANY query, the batch-level ``candidates`` figure).
    Replaces the separate ``mindist_batch`` -> host mask -> gather ->
    ``batch_euclid`` round trips on the serving path.
    """
    mode = _resolve(mode)
    scale = cfg.series_len / cfg.segments
    lower, upper = _finite_bounds(cfg.bits)
    if dead is None:
        dead = jnp.zeros(codes.shape[0], jnp.int32)
    if mode == "jnp":
        return _scan_verify_jit(queries, q_paas, codes, raw, lower, upper,
                                bound, dead, scale=scale, k=k)
    return scan_verify_pallas(queries, q_paas, codes.astype(jnp.int32),
                              raw, lower, upper, bound, dead, scale=scale,
                              k=k, interpret=(mode == "interpret"))


def mesh_scan(queries: jax.Array, q_paas: jax.Array, codes: jax.Array,
              raw: jax.Array, ids: jax.Array, ts: jax.Array,
              ts_min, bound: jax.Array, cfg: S.SummaryConfig, *,
              mesh, axis: str = "shard", k: int = 1,
              mode: str = "auto"):
    """Whole-batch device-resident sharded scan: ONE ``shard_map``
    launch running per-device prune + verify + top-k over every shard's
    pinned ``[S, cap, ...]`` column stacks, merged on device.

    ``ts_min`` is a per-shard ``[S]`` int32 visibility cut or None (no
    window filtering compiled in).  Returns (dists ``[Q, k]``, global
    ids ``[Q, k]`` int32 with -1 padding, counts ``[S, Q]`` int32).
    On TPU/GPU with one sub-shard per device the per-device body is the
    fused ``scan_verify`` Pallas kernel; everywhere else it is the jnp
    twin with identical formulas.  Oracle: ``ref.mesh_scan_ref``.
    """
    mode = _resolve(mode)
    ts_filter = ts_min is not None
    if ts_min is None:
        ts_min = jnp.zeros(ids.shape[0], jnp.int32)
    fn = _mesh.mesh_scan_launch(mesh, axis, cfg, k=k,
                                ts_filter=ts_filter, mode=mode)
    return fn(codes, raw, ids, ts, ts_min, queries, q_paas, bound)


def summarize_and_key(x: jax.Array, cfg: S.SummaryConfig,
                      mode: str = "auto"):
    """Fused construction pass: raw -> (paa, codes, keys) in one sweep."""
    paa, codes = sax_summarize(x, cfg, mode=mode)
    keys = zorder(codes.astype(jnp.uint8), cfg, mode=mode)
    return paa, codes, keys
