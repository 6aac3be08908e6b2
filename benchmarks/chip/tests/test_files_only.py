"""A configuration kind, a traffic loop, their control and a cell are
added to a copy of the benchmark as new files and new entries alone, and
run: the program reads correct and the control does not, and no file
the copy held before is changed.

The additions are under ``tests/toy/``: a kind whose system scans every
row with the plain reference, block by block, and whose control scans
in bfloat16; a loop that makes its collection in one block per device;
a configuration, a traffic mix and two metrics; their entries in
``entries.json``.  The cell runs in a process of its own, once on one
CPU device and once on four.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from typing import Dict

import pytest

HERE = Path(__file__).resolve().parent.parent
ROOT = HERE.parent.parent
TOY = Path(__file__).resolve().parent / "toy"
CELL = "toy-blocks-q16"


def _digests(root: Path) -> Dict[str, str]:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def assemble(dst: Path) -> Dict[str, str]:
    """A checkout of the benchmark in ``dst`` with the toy's files and
    entries added.  Returns the digest of every file the copy held
    before the additions."""
    bench_dir = dst / "benchmarks" / "chip"
    shutil.copytree(HERE, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    before = _digests(dst)
    for f in sorted(TOY.rglob("*")):
        rel = f.relative_to(TOY)
        if f.is_file() and rel.name != "entries.json" \
                and "__pycache__" not in rel.parts:
            target = bench_dir / rel
            assert not target.exists(), f"{rel} is not a new file"
            target.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(f, target)
    bench = json.loads((dst / "BENCHMARK.json").read_text())
    for group, entries in json.loads(
            (TOY / "entries.json").read_text()).items():
        bench[group] += entries
    (dst / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return before


# run in the copy: the program through the harness, then the control
# through control.py; the CPU stands in for the chip, so there is no
# chip to require and no chip's peaks to read
DRIVE = r"""
import json, sys, time
sys.path.insert(0, "benchmarks/chip")
import chipenv, harness, roofline, systems
import jax
chipenv.require_tpu = lambda chips=1: {
    "platform": "cpu", "kind": jax.devices()[0].device_kind,
    "count": len(jax.devices())}
roofline.peaks = lambda kind: {}
bench = harness.load_bench()
cell, entry, config, traffic = harness.cell_of(bench, CELL)
config, traffic = systems.of(config["kind"]).cpu_size(config, traffic)
harness.cell_of = lambda b, w: (cell, entry, config, traffic)
out, info = harness.run_cell(bench, CELL, 2 ** 33 + 7, 0.3, False,
                             time.perf_counter())
print("PROGRAM " + json.dumps({"out": out, "info": info}), flush=True)
import control
control.main(["--workload", CELL, "--seconds", "0.3", "--side",
              "control", "--seeds", "12345678901"])
"""


@pytest.mark.parametrize("devices", [1, 4])
def test_new_kind_loop_control_and_cell_from_files_alone(tmp_path,
                                                         devices):
    before = assemble(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)),
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{devices}")
    p = subprocess.run([sys.executable, "-c",
                        f"CELL = {CELL!r}\n" + DRIVE], cwd=tmp_path,
                       env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = p.stdout.strip().splitlines()
    prog = json.loads(lines[-2].split(" ", 1)[1])
    ctrl = json.loads(lines[-1])
    assert prog["out"]["correct"], prog["out"]["checks"]
    assert prog["out"]["failed"] == 0
    assert prog["out"]["metrics"]["toy_qps"]["value"] > 0
    assert set(prog["out"]["metrics"]) == {"toy_qps", "setup_s"}
    blocks = prog["info"]["block_devices"]
    assert len(blocks) == len(set(blocks)) == devices
    assert ctrl["side"] == "control" and ctrl["correct"] is False
    assert ctrl["checks"]["dist_ulp"] > 0
    after = _digests(tmp_path)
    changed = [f for f, h in before.items()
               if f != "BENCHMARK.json" and after.get(f) != h]
    assert changed == []
    # BENCHMARK.json: every entry it had, as it was, and the new ones
    old = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = json.loads((tmp_path / "BENCHMARK.json").read_text())
    assert set(new) == set(old)
    for group, entries in old.items():
        if isinstance(entries, list) and entries and \
                isinstance(entries[0], dict):
            assert new[group][:len(entries)] == entries
        else:
            assert new[group] == entries
