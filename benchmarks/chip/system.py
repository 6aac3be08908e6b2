"""What the systems under test of every kind share.

This module and the kinds' files under ``systems/`` are the only
modules of the benchmark that import the program (``src/repro``).  Here:
the summary of a configuration, the counts of a probe, the rounding of
the controls, and the program's spans and counters.  The loops call the
program only through a kind's system object, so the control and the
fault tests can put something else in the program's place without
touching the loops.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax.numpy as jnp


def summary_config(config: dict):
    from repro.core.summarization import SummaryConfig
    return SummaryConfig(series_len=config["series_len"],
                         segments=config["segments"], bits=config["bits"])


def search_counts(stats) -> Dict[str, int]:
    """The counts of one probe that the per-layer readers use."""
    return {"leaves_scanned": int(stats.leaves_scanned),
            "leaves_pruned": int(stats.leaves_pruned),
            "candidates": int(stats.candidates),
            "scan_bytes": int(stats.scan_bytes)}


def bf16_round(x):
    """``x`` rounded to bfloat16, in float32: one precision below the
    float32 every configuration states, as the controls use it."""
    return jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)


# ---------------------------------------------------------- observability
def enable_spans(capacity: int = 1 << 21) -> None:
    from repro.obs import enable_tracing, get_tracer
    get_tracer().clear()
    enable_tracing(capacity)


def take_spans() -> Tuple[List[dict], float, int]:
    """Finished spans (``ts``/``dur`` in microseconds from the tracer's
    epoch), the epoch on ``time.perf_counter``'s clock, and how many
    spans the ring dropped; stops tracing."""
    from repro.obs import disable_tracing, get_tracer
    tr = get_tracer()
    disable_tracing()
    return tr.spans(), tr.epoch, tr.dropped


def registry_snapshot() -> Dict[str, float]:
    """Counters by name, histograms as ``name.count`` / ``name.sum``."""
    from repro.obs import get_registry
    return get_registry().snapshot()
