"""The sharded engine over a collection spread across devices: shard
placement (shard ``i`` on device ``i * D // S``), ``bulk_load`` of
blocks made on the devices, and the mesh pin built from the shards'
device columns with no host mirror.

Multi-device cases run in subprocesses with
``--xla_force_host_platform_device_count`` (the device count locks at
the first jax init), once on one device and once on four; the launch's
per-device body is the jnp twin, or the Pallas ``scan_verify`` kernel in
interpret mode (``COCONUT_KERNEL_MODE=interpret``: one shard per device,
so four devices take the kernel).
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]

# the plain reference: float32 squares, each rounded on its own, added
# pairwise in the documented fixed order (element i with i + h, h = L/2
# .. 1), ties to the lower id; numpy only, none of the program's kernels
BRUTE = """
import numpy as np

def brute(rows, queries, k):
    s = rows[None, :, :] - queries[:, None, :]
    s = s * s
    while s.shape[-1] > 1:
        h = s.shape[-1] // 2
        s = s[..., :h] + s[..., h:]
    d = s[..., 0]
    ids = np.broadcast_to(np.arange(len(rows)), d.shape)
    order = np.lexsort((ids, d), axis=1)[:, :k]
    return np.take_along_axis(d, order, 1), order.astype(np.int64)
"""


def _run(code: str, devices: int, mode: str = "jnp", timeout: int = 520):
    """``code`` (indented as in the test) after the brute force and the
    loader, in a process of its own on ``devices`` CPU devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["COCONUT_KERNEL_MODE"] = mode
    r = subprocess.run([sys.executable, "-c",
                        BRUTE + LOAD + textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=REPO)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


LOAD = """
import jax, jax.numpy as jnp
from repro.core import summarization as S
from repro.distributed.sharded_lsm import ShardedCoconutLSM
from repro.obs.registry import get_registry

cfg = S.SummaryConfig(series_len=64, segments=8, bits=4)
devs = jax.devices()
rng = np.random.default_rng(5)
host = [rng.standard_normal((1200, 64)).astype(np.float32) for _ in devs]
rows = np.concatenate(host)

def loaded(**kw):
    eng = ShardedCoconutLSM(cfg, shards=4, leaf_size=64,
                            buffer_capacity=256, **kw)
    # one block made on each device; the engine consumes them
    eng.bulk_load([jax.device_put(h, d) for h, d in zip(host, devs)])
    return eng

def devices_of(eng):
    return [{d for r in s.runs
             for a in jax.tree_util.tree_leaves(r.tree)
             for d in a.devices()} for s in eng._shard_list()]
"""


@pytest.mark.timeout(520)
@pytest.mark.parametrize("devices", [1, 4])
def test_bulk_load_places_each_shard_on_its_device(devices):
    """Every array of shard i's run lives on device i (all on the one
    device of a one-device host), the blocks' rows were copied device to
    device, and a later insert + flush keeps every run there."""
    out = _run("""
        from repro.launch.mesh import make_scan_mesh, shard_device
        # one rule: shard i's device sits at its block's place in the mesh
        for s in (1, 2, 3, 4, 6, 8):
            mesh = list(make_scan_mesh(s).devices.flat)
            spd = s // len(mesh)
            assert [shard_device(i, s) for i in range(s)] == \
                [mesh[i // spd] for i in range(s)], s
        from repro.obs import disable_tracing, enable_tracing, get_tracer
        ex0 = get_registry().counter("shard.exchange_bytes_total").value
        enable_tracing(1 << 12)
        eng = loaded()
        spans = get_tracer().spans()
        disable_tracing()
        sent = get_registry().counter("shard.exchange_bytes_total").value
        load, = [s for s in spans if s["name"] == "shard.load"]
        kids = [s for s in spans if s["name"] in (
            "shard.route", "shard.exchange", "shard.build")]
        assert len(kids) == 1 + len(devs) + 4, [s["name"] for s in kids]
        assert all(s["parent"] == load["id"] for s in kids)
        want = [{shard_device(i, 4)} for i in range(4)]
        assert devices_of(eng) == want, devices_of(eng)
        assert sum(eng.shard_sizes()) == len(rows) == eng.n
        assert eng._next_id == eng.clock == len(rows)
        assert (sent > ex0) == (len(devs) > 1), (sent, ex0)
        before = [[r.tree for r in s.runs] for s in eng._shard_list()]
        eng.insert(rng.standard_normal((700, 64)).astype(np.float32))
        eng.flush()
        assert devices_of(eng) == want, devices_of(eng)
        assert eng.n == len(rows) + 700
        # every shard flushed a run (and merged it where its level filled)
        assert all([id(r.tree) for r in s.runs] != [id(t) for t in b]
                   for s, b in zip(eng._shard_list(), before))
        print("placed-ok", len(devs))
        """, devices)
    assert f"placed-ok {devices}" in out


@pytest.mark.timeout(520)
@pytest.mark.parametrize("mode", ["jnp", "interpret"])
@pytest.mark.parametrize("devices", [1, 4])
def test_exact_answers_equal_plain_brute_force(devices, mode):
    """Over the same rows, bulk_load + mesh, bulk_load + threaded and
    insert + threaded all return the plain float32 brute force's
    distances and ids, bit for bit."""
    out = _run("""
        queries = np.concatenate([
            rows[rng.integers(0, len(rows), 6)]
            + 0.1 * rng.standard_normal((6, 64)).astype(np.float32),
            rng.standard_normal((4, 64)).astype(np.float32)])
        k = 10
        ref_d, ref_i = brute(rows, queries, k)
        bulk = loaded(scan_mode="mesh")
        ins = ShardedCoconutLSM(cfg, shards=4, leaf_size=64,
                                buffer_capacity=256)
        for h in host:
            ins.insert(h)
        ins.flush()
        got = {
            "bulk+mesh": bulk.search_exact_batch(queries, k=k),
            "bulk+threaded": bulk.search_exact_batch(
                queries, k=k, scan_mode="threaded"),
            "insert+threaded": ins.search_exact_batch(queries, k=k),
        }
        assert got["bulk+mesh"][2]["scan_mode"] == "mesh"
        for name, (d, i, _) in got.items():
            np.testing.assert_array_equal(d, ref_d, err_msg=name)
            np.testing.assert_array_equal(i, ref_i, err_msg=name)
        print("exact-ok")
        """, devices, mode)
    assert "exact-ok" in out


def _small_loaded(**kw):
    import jax
    from repro.core import summarization as S
    from repro.distributed.sharded_lsm import ShardedCoconutLSM
    cfg = S.SummaryConfig(series_len=64, segments=8, bits=4)
    rng = np.random.default_rng(9)
    rows = rng.standard_normal((2000, 64)).astype(np.float32)
    eng = ShardedCoconutLSM(cfg, shards=2, leaf_size=64,
                            buffer_capacity=256, scan_mode="mesh", **kw)
    eng.bulk_load([jax.numpy.asarray(rows[:1000]),
                   jax.numpy.asarray(rows[1000:])])
    return eng, rows, rng


def test_pinned_shards_hold_no_host_rows():
    """The pinned generation holds device arrays only: no numpy mirror
    of the rows or the ids."""
    import dataclasses
    import jax
    eng, rows, _ = _small_loaded()
    pinned = eng.pin_mesh()
    assert pinned is not None and sum(pinned.rows) == len(rows)
    fields = {f.name: getattr(pinned, f.name)
              for f in dataclasses.fields(pinned)}
    assert not any(isinstance(v, np.ndarray) for v in fields.values())
    for name in ("codes", "raw", "ids", "ts"):
        assert isinstance(fields[name], jax.Array), name
    assert not {"host_raw", "ids_sorted", "id_order"} & set(fields)
    eng.close()


def test_mesh_launch_reads_count_as_device_syncs():
    """The launch's three outputs are read through ``merger.to_host``:
    each is a counted device sync, with its bytes."""
    eng, rows, rng = _small_loaded()
    q = rows[:3] + np.float32(0.01)
    d, ids, info = eng.search_exact_batch(q, k=4)
    assert info["scan_mode"] == "mesh"
    st = info["stats"]
    assert st.device_syncs == 3, st.device_syncs
    assert st.d2h_bytes == 3 * 4 * 4 + 3 * 4 * 4 + 2 * 3 * 4
    np.testing.assert_array_equal(ids[:, 0], [0, 1, 2])
    eng.close()


def test_bulk_load_is_traced_and_refuses_a_filled_engine():
    """``shard.load`` holds the route, one exchange per block and one
    build per shard; a second load into the filled engine is refused."""
    import jax
    from repro.obs import disable_tracing, enable_tracing, get_tracer
    get_tracer().clear()
    enable_tracing(1 << 12)
    try:
        eng, rows, _ = _small_loaded()
    finally:
        spans = get_tracer().spans()
        disable_tracing()
    names = [s["name"] for s in spans]
    assert names.count("shard.load") == 1
    assert names.count("shard.route") == 1
    assert names.count("shard.exchange") == 2
    assert names.count("shard.build") == 2
    ex = [s for s in spans if s["name"] == "shard.exchange"]
    assert all(s["args"]["bytes"] == 0 for s in ex)   # one device
    assert sorted(s["args"]["shard"] for s in spans
                  if s["name"] == "shard.build") == [0, 1]
    with pytest.raises(ValueError, match="empty engine"):
        eng.bulk_load([jax.numpy.asarray(rows[:10])])
    eng.close()
