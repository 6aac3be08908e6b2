"""Compile-only checks of the main-path kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles one kernel at the paper's
widths (``configs/coconut_paper.py``: L=256, w=16, b=8) for a ``v5e:2x2``
topology that is described, not attached.  The chip's compiler then
refuses what interpret mode cannot see — a tile that overflows the 16 MiB
scoped VMEM, a slice the Mosaic tiling rejects.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and the suite's workers
all import this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.coconut_paper import INDEX as CFG
from repro.kernels import mesh_scan
from repro.kernels.scan_verify import scan_verify_pallas
from repro.kernels.unpack_mindist import unpack_mindist_batch_pallas

N, Q, K = 65536, 64, 10
CARD = 1 << CFG.bits
SCALE = CFG.series_len / CFG.segments


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - environment dependent
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_unpack_mindist_compiles(one_chip):
    pw = -(-CFG.segments * CFG.bits // 8)
    fn = jax.jit(lambda q, p, lo, up: unpack_mindist_batch_pallas(
        q, p, lo, up, w=CFG.segments, b=CFG.bits, scale=SCALE,
        interpret=False))
    compiled = fn.lower(
        _sds((Q, CFG.segments), jnp.float32, one_chip),
        _sds((N, pw), jnp.uint8, one_chip),
        _sds((CARD,), jnp.float32, one_chip),
        _sds((CARD,), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_scan_verify_compiles(one_chip):
    L, w = CFG.series_len, CFG.segments
    fn = jax.jit(lambda qs, qp, c, x, lo, up, bd, dead: scan_verify_pallas(
        qs, qp, c, x, lo, up, bd, dead, scale=SCALE, k=K,
        interpret=False))
    compiled = fn.lower(
        _sds((Q, L), jnp.float32, one_chip),
        _sds((Q, w), jnp.float32, one_chip),
        _sds((N, w), jnp.int32, one_chip),
        _sds((N, L), jnp.float32, one_chip),
        _sds((CARD,), jnp.float32, one_chip),
        _sds((CARD,), jnp.float32, one_chip),
        _sds((Q,), jnp.float32, one_chip),
        _sds((N,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mesh_scan_launch_compiles(topo):
    """The four-chip mesh program: one shard per device, so every
    device body is the compiled ``scan_verify`` kernel."""
    devices = np.asarray(topo.devices)
    assert devices.size == 4
    mesh = jax.sharding.Mesh(devices, ("shard",))
    s, cap = 4, N // 4
    L, w = CFG.series_len, CFG.segments
    fn = mesh_scan.mesh_scan_launch(mesh, "shard", CFG, k=K,
                                    ts_filter=True, mode="pallas")
    stack3 = NamedSharding(mesh, P("shard", None, None))
    stack2 = NamedSharding(mesh, P("shard", None))
    stack1 = NamedSharding(mesh, P("shard"))
    rep = NamedSharding(mesh, P())
    compiled = fn.lower(
        _sds((s, cap, w), jnp.uint8, stack3),
        _sds((s, cap, L), jnp.float32, stack3),
        _sds((s, cap), jnp.int32, stack2),
        _sds((s, cap), jnp.int32, stack2),
        _sds((s,), jnp.int32, stack1),
        _sds((Q, L), jnp.float32, rep),
        _sds((Q, w), jnp.float32, rep),
        _sds((Q,), jnp.float32, rep)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text


@pytest.mark.parametrize("program", ["wave_bound", "wave_verify"])
def test_wave_programs_fit_their_byte_cap(one_chip, program):
    """The exact scan's two wave programs at a 2^22-row collection with
    leaves of 2,000 rows and 16 queries: each compiles for one chip and
    its intermediates stay under the executor's wave byte cap."""
    from repro.core.tree import CoconutTree
    from repro.query import Partition
    from repro.query import executor as E
    n, leaf, qp, L, w = 1 << 22, 2000, 16, CFG.series_len, CFG.segments
    part = Partition(kind="tree", backend="device", cfg=CFG, n=n,
                     leaf_size=leaf, source=None)
    width, cap = E._wave_shape(qp, part)
    order = _sds((-(-part.n_leaves // width) * width,), jnp.int32, one_chip)
    best_d = _sds((qp, K), jnp.float32, one_chip)
    if program == "wave_bound":
        compiled = E.wave_bound.lower(
            _sds((n, w), jnp.uint8, one_chip),
            _sds((qp, w), jnp.float32, one_chip), order,
            _sds((order.shape[0] // width,), jnp.float32, one_chip), 3,
            part.n_leaves, best_d, _sds((qp,), jnp.float32, one_chip), None,
            cfg=CFG, leaf_size=leaf, width=width).compile()
    else:
        tree = CoconutTree(
            keys=_sds((n, CFG.n_words), jnp.uint32, one_chip),
            codes=_sds((n, w), jnp.uint8, one_chip),
            paas=_sds((n, w), jnp.float32, one_chip),
            offsets=_sds((n,), jnp.int32, one_chip),
            raw=_sds((n, L), jnp.float32, one_chip), raw_ref=None,
            timestamps=None, cfg=CFG, leaf_size=leaf)
        compiled = E.wave_verify.lower(
            tree, _sds((qp, L), jnp.float32, one_chip), order, 3,
            _sds((1, width * leaf), jnp.uint32, one_chip), 0, best_d,
            _sds((qp, K), jnp.int32, one_chip),
            leaf_size=leaf, width=width, cap=cap).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= E._WAVE_BYTES
