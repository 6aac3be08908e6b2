"""``chip_smoke.py`` off the chip: it must refuse the CPU and a bare
directory, and its phases must pass at tiny sizes on the CPU (jnp and
interpret-mode kernels), so a chip run only finds what needs the chip."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


def _run(script: Path, cwd: Path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["repo", "bare"])
def test_refuses_without_chip_or_repo(where, tmp_path):
    script = REPO / "chip_smoke.py"
    if where == "bare":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    r = _run(script, script.parent)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_brute_topk_and_ulps(smoke):
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((1000, 32)).astype(np.float32)
    qs = rows[[3, 500, 999]] + 0.01
    d, i = smoke.brute_topk(rows, qs, k=5, block=128)
    ed = ((rows[None] - qs[:, None]) ** 2).sum(-1)
    want = np.argsort(ed, axis=1, kind="stable")[:, :5]
    assert np.array_equal(np.asarray(i), want)
    assert smoke.max_ulp(np.asarray(d), np.take_along_axis(
        ed, want, axis=1).astype(np.float32)) <= 4
    one = np.float32(1.0)
    assert smoke.max_ulp(np.array([one]),
                         np.array([np.nextafter(one, np.float32(2))])) == 1


@pytest.mark.timeout(600)
def test_phases_at_tiny_sizes(smoke):
    sz = smoke.Sizes(static_rows=6000, stream_rows=6000, batch=1500,
                     window=3000, queries=4, k=10)
    clock = smoke.CompileClock()
    smoke.run(sz, 0, clock)
