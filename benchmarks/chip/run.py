#!/usr/bin/env python3
"""Run one cell of the Coconut chip benchmark once, from the root of a
checkout:

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cells, their configurations, traffic mixes and metrics are named in
``BENCHMARK.json``.  The run refuses anything but a TPU (exit code other
than 0, no result), keeps JAX's compile cache in ``<checkout>/.jax_cache``
(or ``$JAX_COMPILATION_CACHE_DIR``), and prints one JSON object as the
last line of its standard output; the numbers its check compared, each
with its limit, are the last lines of its standard error and the
``checks`` key of that object.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
