#!/usr/bin/env python3
"""The controls of the benchmark's comparison, and readings on the chip.

A control is something put in the program's place that the check must
refuse, computed one precision below what the configuration states
(bfloat16 for float32):

* probe cells: the plain reference itself, scanning every row with its
  distances in bfloat16, answers the window's calls;
* the build cell: the program's tree stores its rows rounded to
  bfloat16, as a tree that kept them at half width would;
* the ingest cell: the program ingests the rows rounded to bfloat16.

Readings, on the chip, at the cell's own size, one process for every
seed (each seed makes its own data)::

    python3 benchmarks/chip/control.py --workload rand4m-exact-b16 \\
        --seconds 5 --side control --seeds 11 12 13

``--side program`` reads the program itself.  Each run prints one line
with the compared numbers; the benchmark's own runs never run this.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import refknn  # noqa: E402
import system as sysmod  # noqa: E402


def bf16_round(x):
    return jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)


class ProbeControl(sysmod.StaticIndex):
    """The reference in bfloat16 in the program's place: its "index" is
    the collection itself and every call scans all of it."""

    def build(self, raw):
        return raw

    def search(self, tree, queries, *, k, budget):
        d, ids = refknn.brute_topk_blocked(tree, queries, k=k,
                                           qblock=len(queries),
                                           dtype=jnp.bfloat16)
        counts = {"leaves_scanned": 0, "leaves_pruned": 0,
                  "candidates": 0, "scan_bytes": 0}
        gap = None if budget is None else np.zeros(len(queries),
                                                    np.float32)
        return d, ids, counts, gap


class BuildControl(sysmod.StaticIndex):
    """The program's bulk load, its co-sorted rows stored rounded to
    bfloat16 (rounding the collection before the build would hold a
    third 4 GiB copy on a chip that has room for two)."""

    def build(self, raw):
        import dataclasses
        tree = super().build(raw)
        return dataclasses.replace(tree, raw=bf16_round(tree.raw))


class StreamControl(sysmod.StreamIndex):
    """The program's engine fed the rows rounded to bfloat16."""

    def create(self, root):
        return _RoundingWriter(super().create(root))


class _RoundingWriter:
    """The engine, with every insert rounded first."""

    def __init__(self, eng):
        self.eng = eng

    def insert(self, rows):
        self.eng.insert(np.asarray(bf16_round(rows)))

    def __getattr__(self, name):
        return getattr(self.eng, name)


def control_for(config: dict, traffic: dict):
    if config["kind"] == "streaming_lsm":
        return StreamControl(config)
    if traffic["loop"] == "build":
        return BuildControl(config)
    return ProbeControl(config)


def main(argv=None) -> int:
    import argparse
    import json
    import time
    import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--side", choices=("program", "control"),
                    required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = harness.load_bench()
    cell, _, config, traffic = harness.cell_of(bench, args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    from chipenv import CompileClock, require_tpu, use_compile_cache
    use_compile_cache(harness.ROOT / ".jax_cache")
    clock = CompileClock()
    dev = require_tpu(cell["chips"])
    for seed in args.seeds:
        system = (control_for(config, traffic) if args.side == "control"
                  else None)
        t0 = time.perf_counter()
        try:
            out, info = harness.run_cell(bench, args.workload, seed,
                                         args.seconds, False, t0,
                                         system=system, clock=clock,
                                         device_kind=dev["kind"])
        except Exception as e:          # a control that crashes fails
            print(json.dumps({"side": args.side, "seed": seed,
                              "crashed": f"{type(e).__name__}: {e}"}),
                  flush=True)
            continue
        print(json.dumps({"side": args.side, "seed": seed,
                          "correct": out["correct"],
                          "checks": {n: c["value"] for n, c in
                                     out["checks"].items()},
                          "metrics": {n: m["value"] for n, m in
                                      out["metrics"].items()},
                          "info": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
