#!/usr/bin/env python3
"""Readings of the program and of its control on the chip.

A control is something put in the program's place that the check must
refuse, computed one precision below what the configuration states
(bfloat16 for float32).  Each configuration kind's file gives its own
(``systems/<kind>.py``, ``control(config, traffic)``):

* ``static_collection``, probe cells: the plain reference itself,
  scanning every row with its distances in bfloat16, answers the
  window's calls; the build cell: the program's tree stores its rows
  rounded to bfloat16, as a tree that kept them at half width would;
* ``streaming_lsm``: the program ingests the rows rounded to bfloat16.

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


def main(argv=None) -> int:
    import argparse
    import json
    import time
    import harness
    import systems
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
        system = (systems.of(config["kind"]).control(config, traffic)
                  if args.side == "control" else None)
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
