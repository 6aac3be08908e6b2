"""The four-chip sharded collection's cell on the CPU: its kind and
loop read correct at ``cpu_size`` on one and on four devices, and the
control and two planted faults do not (one shard's rows left out of the
pin; every call pushed off the mesh launch onto the threaded fan-out).
Its three per-layer readers are fed a made-up four-plane trace.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent.parent
ROOT = HERE.parent.parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

CELL = "rand16m-exact-mesh4"

# in a process of its own: the program, the control and the planted
# faults through the harness, each printing one line
DRIVE = r"""
import dataclasses, json, sys, time
sys.path.insert(0, "benchmarks/chip")
import harness, systems
import jax, jax.numpy as jnp
from systems import sharded_collection as SC

class DroppedShard(SC.ShardedCollection):
    # shard 0's rows left out of every pinned generation
    def load(self, blocks):
        eng = super().load(blocks)
        me = eng._mesh_engine_get()
        build = me._build
        def lossy(snaps, fp):
            p = build(snaps, fp)
            first = jnp.arange(p.ids.shape[0])[:, None] == 0
            return dataclasses.replace(p, ids=jnp.where(first, -1, p.ids))
        me._build = lossy
        return eng

class OffTheMesh(SC.ShardedCollection):
    # a pin budget no generation fits: every call falls back
    def load(self, blocks):
        from repro.query.mesh import MeshScanEngine
        eng = super().load(blocks)
        eng._mesh_engine = MeshScanEngine(eng.cfg, max_pin_bytes=1)
        return eng

harness.WORK = __import__("pathlib").Path(WORK)
bench = harness.load_bench()
cell, entry, config, traffic = harness.cell_of(bench, CELL)
config, traffic = SC.cpu_size(config, traffic)
harness.cell_of = lambda b, w: (cell, entry, config, traffic)
sides = {"program": None, "control": SC.control(config, traffic),
         "dropped_shard": DroppedShard(config),
         "off_the_mesh": OffTheMesh(config)}
for side, system in sides.items():
    out, info = harness.run_cell(bench, CELL, 2 ** 33 + 7, 0.3, False,
                                 time.perf_counter(), system=system)
    print("SIDE " + json.dumps({"side": side, "out": out, "info": info}),
          flush=True)
"""


@pytest.fixture(scope="module", params=[1, 4])
def sides(request, tmp_path_factory):
    devices = request.param
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)),
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{devices}")
    work = tmp_path_factory.mktemp("work")
    p = subprocess.run(
        [sys.executable, "-c",
         f"CELL = {CELL!r}\nWORK = {str(work)!r}\n" + DRIVE],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out = {}
    for line in p.stdout.splitlines():
        if line.startswith("SIDE "):
            rec = json.loads(line[5:])
            out[rec["side"]] = rec
    return devices, out


def test_program_reads_correct(sides):
    devices, out = sides
    prog = out["program"]
    assert prog["out"]["correct"], prog["out"]["checks"]
    assert prog["out"]["failed"] == 0
    assert prog["out"]["metrics"]["probe_qps"]["value"] > 0
    info = prog["info"]
    assert info["chips"] == devices == info["mesh_devices"]
    assert sum(info["rows_per_shard"]) == 1 << 13
    assert (info["exchange_bytes"] > 0) == (devices > 1)
    assert set(info["setup"]) == {"data_s", "load_s", "pin_s",
                                  "warmup_s"}


def test_control_reads_incorrect(sides):
    _, out = sides
    ctrl = out["control"]["out"]
    assert ctrl["failed"] == 0 and not ctrl["correct"]
    assert ctrl["checks"]["dist_ulp"]["value"] > 0


@pytest.mark.parametrize("fault,check", [("dropped_shard", "ids_wrong"),
                                         ("off_the_mesh", "mesh_fallbacks")])
def test_planted_fault_reads_incorrect(sides, fault, check):
    _, out = sides
    got = out[fault]["out"]
    assert got["failed"] == 0 and not got["correct"]
    assert got["checks"][check]["value"] > 0


# ---------------------------------------------- readers on a made-up trace
MAIN = 7
PLANES = [f"/device:TPU:{j}" for j in range(4)]


def _view(**kw):
    calls = [{"queries": 16, "counts": {"rows_pinned": 1 << 20,
                                        "rows_verified": 1 << 20,
                                        "mesh_fallbacks": 0}}] * 2
    # each plane runs the launch program 10 ms a call, from 1.0 and 2.0,
    # plane j 1 ms later than plane j-1; one run lies past the window
    progs = {p: [("jit_mesh_scan(42)", c + j * 1e-3, c + j * 1e-3 + 0.01)
                 for c in (1.0, 2.0)] + [("jit_mesh_scan(42)", 11.0, 11.5)]
             for j, p in enumerate(PLANES)}
    ops = {p: [("fusion", s, e) for _, s, e in v]
           for p, v in progs.items()}
    base = dict(kind="probe",
                config={"segments": 16, "series_len": 256},
                traffic={}, calls=calls, window=(0.0, 10.0), setup_s=1.0,
                extra={}, counters={},
                spans=[("mesh_launch", 0.99, 1.02, MAIN),
                       ("mesh_launch", 1.99, 2.03, MAIN),
                       ("mesh_launch", 5.0, 6.0, MAIN + 1)],
                device_ops=ops, device_programs=progs,
                peaks={"hbm_bytes_per_s": 819e9}, main_thread=MAIN)
    base.update(kw)
    return harness.RunView(**base)


def test_mesh_launch_ms_reads_the_main_threads_spans():
    read = harness.reader("mesh_launch_ms.probe")
    assert read(_view()) == pytest.approx((0.03 + 0.04) * 1e3 / 2)
    assert read(_view(spans=[])) is None          # the parent's program
    assert read(_view(kind="build")) is None


def test_mesh_scan_hbm_share_from_four_planes():
    read = harness.reader("mesh_scan_hbm_share.probe")
    nbytes = 2 * ((1 << 20) * 16 + (1 << 20) * 1024)
    # four planes, each 2 x 10 ms of the launch inside the window
    want = 100.0 * nbytes / (4 * 819e9 * 0.02)
    got = read(_view())
    assert got == pytest.approx(want)
    assert 0 < got <= 100
    assert read(_view(device_programs={p: [] for p in PLANES})) is None
    assert read(_view(peaks=None)) is None


def test_device_idle_mesh_averages_the_planes():
    read = harness.reader("device_idle.mesh")
    view = _view()
    assert read(view) == pytest.approx(100.0 * (1 - 0.02 / 10.0))
    # a plane that ran nothing in the window is idle all of it
    ops = dict(view.device_ops, **{"/device:TPU:4": []})
    assert read(_view(device_ops=ops)) == pytest.approx(
        100.0 * (4 * (1 - 0.002) + 1) / 5)
    assert read(_view(device_ops=None)) is None
