"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips across 2 pods; the
``pod`` axis carries data parallelism (gradient all-reduce crosses DCI) and
FSDP sharding of parameters/optimizer state.

A FUNCTION, not a module constant, so importing this module never touches
jax device state (device count is locked at first jax init — the dry-run
sets XLA_FLAGS before any import).
"""
from __future__ import annotations

import os

import jax

__all__ = ["make_production_mesh", "make_scan_mesh", "scan_devices",
           "shard_device", "dp_axes", "fsdp_axes", "tp_axis"]

SCAN_AXIS = "shard"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            f"run under XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    # more devices than the mesh needs (single-pod mesh under the 512-device
    # dry-run env): carve the leading sub-grid
    import numpy as np
    return jax.sharding.Mesh(
        np.asarray(devices[:n]).reshape(shape), axes)


def scan_devices(n_shards: int) -> list:
    """The D devices that ``n_shards`` key-range shards live on and the
    sharded scan spans: D is the largest divisor of ``n_shards`` that
    fits the available devices, so the pinned ``[S, cap, ...]`` stacks
    always shard evenly (each device holds ``S/D`` shards; with one
    device every shard count degenerates to a single-device launch).
    The ``COCONUT_MESH_DEVICES`` env var caps D below the physical
    device count (ops/bench knob for device-scaling sweeps).
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    devices = jax.devices()
    cap = int(os.environ.get("COCONUT_MESH_DEVICES", "0") or 0)
    if cap > 0:
        devices = devices[:cap]
    d = max(x for x in range(1, min(n_shards, len(devices)) + 1)
            if n_shards % x == 0)
    return list(devices[:d])


def shard_device(i: int, n_shards: int):
    """The device of shard ``i`` of ``n_shards``: ``devices[i * D // S]``
    over :func:`scan_devices`.  The sharded engine keeps every array of
    shard ``i``'s runs there, and :func:`make_scan_mesh` puts the same
    device at the shard's place in the mesh, so a pinned block is built
    where its rows already are."""
    devices = scan_devices(n_shards)
    return devices[i * len(devices) // n_shards]


def make_scan_mesh(n_shards: int, *, axis: str = SCAN_AXIS):
    """1-D mesh over :func:`scan_devices` for the device-resident
    sharded scan of ``n_shards``: mesh position ``d`` holds shards
    ``d*S/D .. (d+1)*S/D - 1``, each on its :func:`shard_device`."""
    import numpy as np
    return jax.sharding.Mesh(np.asarray(scan_devices(n_shards)), (axis,))


def make_host_mesh():
    """Degenerate 1-device mesh for CPU smoke runs of the sharded code."""
    return jax.make_mesh((1, 1), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """Axes carrying data parallelism (batch sharding)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def fsdp_axes(mesh) -> tuple:
    """Axes over which parameters/optimizer state are fully sharded."""
    return dp_axes(mesh)


def tp_axis(mesh) -> str:
    return "model"
