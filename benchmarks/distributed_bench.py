"""Beyond-paper: distributed-index scaling (sample-sort build + exact
query) across host-device shard counts.

Runs in subprocesses held to the CPU (device count is locked per process,
and a child must never reach for a chip its parent may hold).  Reports build
and query wall time per shard count plus partition balance — the paper's
"parallel UB-tree building" future work, measured.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

from .common import emit

REPO = Path(__file__).resolve().parents[1]

_CODE = """
import time, jax, jax.numpy as jnp, numpy as np
from repro.core import summarization as S
from repro.data.series import random_walk
from repro.distributed.sharded_index import build_sharded, \\
    distributed_exact_search
d = __D__
mesh = jax.make_mesh((d, 1), ("data", "model"))
cfg = S.SummaryConfig(series_len=64, segments=8, bits=4)
raw = random_walk(jax.random.PRNGKey(0), 32768, 64)
t0 = time.perf_counter()
tree = build_sharded(mesh, raw, cfg)
tree.keys.block_until_ready()
t_build = time.perf_counter() - t0
q = np.asarray(raw[777])
distributed_exact_search(tree, q, k=1)  # warmup/compile
t0 = time.perf_counter()
for _ in range(5):
    dist, rows = distributed_exact_search(tree, q, k=1)
    dist.block_until_ready()
t_query = (time.perf_counter() - t0) / 5
counts = np.asarray(tree.counts)
print(f"RESULT {t_build*1e6:.1f} {t_query*1e6:.1f} "
      f"{counts.max()/max(counts.mean(),1):.3f}")
"""


def main() -> None:
    for d in (1, 2, 4, 8):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
        env["PYTHONPATH"] = str(REPO / "src")
        r = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(_CODE.replace("__D__", str(d)))],
            capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
        line = [l for l in r.stdout.splitlines()
                if l.startswith("RESULT")]
        if not line:
            emit(f"distributed/shards{d}", 0.0,
                 f"FAILED:{r.stderr[-120:]}")
            continue
        t_build, t_query, imbalance = line[0].split()[1:]
        emit(f"distributed/build/shards{d}", float(t_build),
             f"imbalance={imbalance}")
        emit(f"distributed/query/shards{d}", float(t_query), "")


if __name__ == "__main__":
    main()
