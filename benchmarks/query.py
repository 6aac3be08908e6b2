"""Paper Fig. 13: query performance and approximate quality.

  13a  exact query wall time vs data size: Coconut-TreeSIMS vs brute force
       (the sequential-scan strawman) vs unsorted-summaries SIMS (the ADS+
       analogue: same pruning, no contiguity => random candidate access).
  13b  approximate query time vs data size.
  13c/d approximate radius sweep: time vs accuracy (CTree(r) variants).
  13e/f records visited during exact search (pruning effectiveness).

Also validates the sortability claim from Fig. 2/4: z-ordered approximate
search must beat lexicographic-SAX approximate search at equal cost.

Beyond the paper: a queries-per-second vs batch-size sweep for the batched
multi-query engine (``exact_search_batch`` — one amortized SIMS scan for
the whole batch), the throughput lever for serving traffic.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

import numpy as np

import jax.numpy as jnp

from repro.core import keys as K, summarization as S, tree as T
from repro.kernels import ops

from .common import ROOT, ROWS, block, cfg_for, dataset, emit, timeit, \
    write_bench


def _exact_bruteforce(raw, q):
    return float(jnp.min(S.euclidean_sq(q, raw)))


def bench_query(sizes=(4000, 16000, 64000), *, smoke=False) -> None:
    cfg = cfg_for()
    leaf = 64
    queries = dataset(16, seed=9)
    for n in sizes:
        raw = dataset(n)
        tree = T.build(raw, cfg, leaf_size=leaf)

        q = queries[0]
        us_bf = timeit(lambda: block(S.euclidean_sq(q, raw)))
        emit(f"query/bruteforce/n{n}", us_bf, "")

        def run_exact():
            d, off, st = T.exact_search(tree, q)
            return d
        us_ex = timeit(run_exact, repeat=2)
        d, off, st = T.exact_search(tree, q)
        emit(f"query/ctree_sims_exact/n{n}", us_ex,
             f"pruned={st.pruned_frac:.3f};cands={st.candidates};"
             f"leaves={st.leaves_touched};"
             f"leaves_pruned={st.leaves_pruned};"
             f"leaves_scanned={st.leaves_scanned}")
        if smoke:
            # planner regression guards: the leaf-fence bounds must
            # actually skip leaves, and the per-query verified-candidate
            # count must stay a small fraction of the dataset
            assert st.leaves_pruned > 0, st
            assert st.candidates <= n * 0.2, st

        us_ap = timeit(lambda: T.approx_search(tree, q)[0], repeat=2)
        emit(f"query/ctree_approx/n{n}", us_ap, "")

        # correctness cross-check
        bf = _exact_bruteforce(raw, q)
        d = float(d[0])
        assert abs(bf - d) < 1e-3, (bf, d)
    if smoke:
        return                      # CI smoke: skip the sweeps below

    # ---- radius sweep (Fig. 13c/d) ----------------------------------------
    n = 16000
    raw = dataset(n)
    tree = T.build(raw, cfg, leaf_size=leaf)
    for radius in (1, 2, 10):
        errs, times = [], []
        T.approx_search(tree, queries[0], radius_leaves=radius)  # warmup jit
        for qi in range(8):
            q = queries[qi]
            us = timeit(lambda: T.approx_search(
                tree, q, radius_leaves=radius)[0], repeat=1)
            d_ap, _, _ = T.approx_search(tree, q, radius_leaves=radius)
            d_ex = _exact_bruteforce(raw, q)
            errs.append(np.sqrt(float(d_ap[0]))
                        / max(np.sqrt(d_ex), 1e-9))
            times.append(us)
        emit(f"query/approx_radius{radius}/n{n}", float(np.mean(times)),
             f"dist_ratio={np.mean(errs):.3f}")

    # ---- sortability ablation (Fig. 2/4): z-order vs lexicographic SAX ----
    paas, codes = S.summarize(raw, cfg)
    lex_order = np.lexsort(np.asarray(codes).T[::-1])   # segment-major sort
    raw_lex = raw[jnp.asarray(lex_order)]
    tree_lex = T.CoconutTree(
        keys=tree.keys,  # placeholder keys; approx uses position only
        codes=codes[jnp.asarray(lex_order)],
        paas=paas[jnp.asarray(lex_order)],
        offsets=jnp.asarray(lex_order, jnp.int32),
        raw=raw_lex, raw_ref=None, timestamps=None, cfg=cfg,
        leaf_size=leaf)
    # emulate lexicographic approximate search: locate by first-segment
    # order, fetch the same number of candidates
    ratios_z, ratios_lex = [], []
    for qi in range(16):
        q = queries[qi]
        d_ex = _exact_bruteforce(raw, q)
        d_z, _, _ = T.approx_search(tree, q)
        d_z = float(d_z[0])
        _, q_codes = S.summarize(q[None, :], cfg)
        pos = int(np.searchsorted(
            np.asarray(codes)[lex_order][:, 0], np.asarray(q_codes)[0, 0]))
        lo = max(0, min(pos - leaf, n - 2 * leaf))
        cand = raw_lex[lo: lo + 2 * leaf]
        d_lex = float(jnp.min(S.euclidean_sq(q, cand)))
        ratios_z.append(np.sqrt(d_z / max(d_ex, 1e-12)))
        ratios_lex.append(np.sqrt(d_lex / max(d_ex, 1e-12)))
    emit("query/sortability_ablation", 0.0,
         f"zorder_dist_ratio={np.mean(ratios_z):.3f};"
         f"lexicographic_dist_ratio={np.mean(ratios_lex):.3f}")


def bench_batched_query(n: int = 16000,
                        batch_sizes=(1, 8, 64)) -> None:
    """Queries/sec vs batch size: looped single-query exact search vs ONE
    amortized batched scan (the batched engine's reason to exist)."""
    cfg = cfg_for()
    leaf = 64
    raw = dataset(n)
    tree = T.build(raw, cfg, leaf_size=leaf)
    for q_batch in batch_sizes:
        queries = dataset(q_batch, seed=11)
        # warmup (jit of the batched probe + scan shapes)
        T.exact_search_batch(tree, queries)

        def run_batched():
            d, off, _ = T.exact_search_batch(tree, queries)
            return d
        us_b = timeit(run_batched, repeat=2)
        qps_b = q_batch / (us_b / 1e6)

        def run_looped():
            return [T.exact_search(tree, queries[i])[0]
                    for i in range(q_batch)]
        us_l = timeit(run_looped, repeat=2)
        qps_l = q_batch / (us_l / 1e6)
        emit(f"query/batched_exact/Q{q_batch}/n{n}", us_b,
             f"qps={qps_b:.1f};looped_qps={qps_l:.1f};"
             f"speedup={us_l / us_b:.2f}x")

        # parity spot-check against the single-query path
        d_b, off_b, _ = T.exact_search_batch(tree, queries)
        for i in range(q_batch):
            d_s, off_s, _ = T.exact_search(tree, queries[i])
            assert abs(float(d_b[i, 0]) - float(d_s[0])) < 1e-3, \
                (i, d_b[i, 0], d_s)
            assert int(off_b[i, 0]) == int(off_s[0]), \
                (i, off_b[i, 0], off_s)


def _mesh_sweep_impl(n: int = 64000, nq: int = 64, k: int = 10,
                     shards: int = 4, *, smoke: bool = False):
    """QPS vs device count for the device-resident sharded scan: one
    threaded reference, then the mesh launch at D in {1, 2, 4} devices,
    as far as this process holds them (``COCONUT_MESH_DEVICES`` caps the
    scan mesh below the device count, so one process sweeps the whole
    curve).  Answers are parity-checked against the threaded fan-out at
    every point.  Returns (rows, gates); the 4-device gate exists only
    where 4 devices were swept."""
    import jax
    from repro.distributed.sharded_lsm import ShardedCoconutLSM
    sweep = [d for d in (1, 2, 4) if d <= jax.device_count()]
    cfg = cfg_for()
    raw = np.asarray(dataset(n))
    queries = np.asarray(dataset(nq, seed=11))
    eng = ShardedCoconutLSM(cfg, shards=shards, buffer_capacity=8192,
                            leaf_size=64)
    eng.insert(raw, np.arange(n, dtype=np.int64))
    eng.flush()
    rows = []
    tag = f"n{n}Q{nq}k{k}"

    dt, it, _ = eng.search_exact_batch(queries, k=k,
                                       scan_mode="threaded")  # warm
    us_t = timeit(lambda: eng.search_exact_batch(
        queries, k=k, scan_mode="threaded"), repeat=3)
    rows.append((f"query/mesh_sweep/threaded/{tag}", us_t,
                 f"qps={nq / (us_t / 1e6):.1f};shards={shards}"))
    us_mesh = {}
    for d in sweep:
        os.environ["COCONUT_MESH_DEVICES"] = str(d)
        try:
            eng._mesh_engine = None     # re-pin under the device cap
            dm, im, inf = eng.search_exact_batch(queries, k=k,
                                                 scan_mode="mesh")
            assert inf["scan_mode"] == "mesh", inf
            assert inf["mesh_devices"] == d, inf
            np.testing.assert_array_equal(dm, dt)
            np.testing.assert_array_equal(im, it)
            us = timeit(lambda: eng.search_exact_batch(
                queries, k=k, scan_mode="mesh"), repeat=3)
        finally:
            del os.environ["COCONUT_MESH_DEVICES"]
        us_mesh[d] = us
        rows.append((f"query/mesh_sweep/mesh_d{d}/{tag}", us,
                     f"qps={nq / (us / 1e6):.1f};devices={d};"
                     f"speedup={us_t / us:.2f}x"))
    eng.close()
    gates = []
    if 4 in us_mesh:
        speedup = us_t / us_mesh[4]
        gates = [{"name": "mesh_vs_threaded_d4", "value": speedup,
                  "min": 1.3}]
        if smoke:
            # the scaling claim, asserted at bench time: with >= 2
            # devices the one-launch scan must beat the threaded fan-out
            assert us_mesh[2] < us_t, (us_mesh, us_t)
            assert speedup >= 1.3, (us_mesh, us_t)
    for name, us, derived in rows:
        emit(name, us, derived)
    return rows, gates


def bench_mesh_devices(*, smoke: bool = False):
    """Run the mesh device sweep over the devices this process holds.

    An accelerator belongs to the process that touched it first, so on
    one the sweep stays here, over the chips it has.  Only on the CPU,
    whose device count is fixed at first jax init, does it re-exec into
    a child held to the CPU with 4 forced host devices."""
    import jax
    if jax.default_backend() != "cpu" or jax.device_count() >= 4:
        _rows, gates = _mesh_sweep_impl(smoke=smoke)
        return gates
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        out_path = f.name
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.setdefault("PYTHONPATH", str(ROOT / "src"))
    cmd = [sys.executable, "-m", "benchmarks.query",
           "--mesh-sweep-child", out_path] + (["--smoke"] if smoke else [])
    try:
        r = subprocess.run(cmd, env=env, cwd=ROOT, capture_output=True,
                           text=True, timeout=1800)
        assert r.returncode == 0, \
            f"mesh sweep child failed\nstdout:\n{r.stdout}" \
            f"\nstderr:\n{r.stderr}"
        doc = json.loads(open(out_path).read())
    finally:
        os.unlink(out_path)
    for row in doc["rows"]:
        emit(row["name"], row["us_per_call"], row["derived"])
    return doc["gates"]


def main(smoke: bool = False) -> None:
    before = len(ROWS)
    if smoke:
        # tiny planner-regression smoke for CI: one size, batch parity
        bench_query(sizes=(4000,), smoke=True)
        bench_batched_query(n=4000, batch_sizes=(1, 8))
    else:
        bench_query()
        bench_batched_query()
    # the device-scaling sweep runs in smoke too: its rows are blessed
    # baseline coverage and its gate (mesh >= 1.3x threaded at 4
    # devices on the 64k batch probe) is a hard CI check via regress.py
    gates = bench_mesh_devices(smoke=smoke)
    write_bench("query", payload={"smoke": smoke, "gates": gates},
                rows=ROWS[before:])


if __name__ == "__main__":
    if "--mesh-sweep-child" in sys.argv:
        out = sys.argv[sys.argv.index("--mesh-sweep-child") + 1]
        rows, gates = _mesh_sweep_impl(smoke="--smoke" in sys.argv)
        with open(out, "w") as f:
            json.dump({"rows": [{"name": n, "us_per_call": u,
                                 "derived": d} for n, u, d in rows],
                       "gates": gates}, f)
    else:
        main()
