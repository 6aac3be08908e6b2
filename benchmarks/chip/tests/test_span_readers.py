"""The readers of the program's executor and write-path spans and
counters, fed made-up runs on the CPU: each reads its number from the
main thread's spans inside the window, and nothing outside its cells or
from a program that records no such span or counter.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

MAIN = 7


def _view(kind, spans, counters=None, calls=None):
    return harness.RunView(kind=kind, config={}, traffic={},
                           calls=calls or [], window=(0.0, 10.0),
                           setup_s=1.0, extra={}, counters=counters or {},
                           spans=spans, main_thread=MAIN)


# two probe calls of 16 queries; the second call's sync spans overlap,
# one span runs on another thread and one past the window
PROBE = _view("probe", [
    ("exec.sync", 1.0, 1.002, MAIN), ("exec.sync", 1.001, 1.004, MAIN),
    ("exec.pool", 1.004, 1.005, MAIN), ("exec.sync", 2.0, 2.001, MAIN),
    ("exec.pool", 2.001, 2.003, MAIN), ("exec.sync", 3.0, 4.0, MAIN + 1),
    ("exec.pool", 9.9, 10.5, MAIN), ("exec.launch", 0.9, 1.0, MAIN),
    ("exec.launch", 1.9, 2.0, MAIN), ("exec.launch", -0.1, 0.1, MAIN)],
    counters={"query.device_syncs_total": 96},
    calls=[{"queries": 16}, {"queries": 16},
           {"queries": 16, "error": "x"}])

# two rounds, five acked batches, two commits
INGEST = _view("ingest", [
    ("insert", 1.0, 1.5, MAIN), ("wal.append", 1.0, 1.2, MAIN),
    ("fsync", 1.1, 1.2, MAIN), ("wal.append", 2.0, 2.1, MAIN),
    ("fsync", 2.05, 2.1, MAIN), ("compact.commit", 3.0, 4.0, MAIN),
    ("segment.fetch", 3.0, 3.3, MAIN), ("fsync", 3.4, 3.5, MAIN),
    ("compact.commit", 5.0, 6.0, MAIN), ("segment.fetch", 5.0, 5.1, MAIN),
    ("wal.append", 6.0, 6.3, MAIN + 1), ("fsync", 7.0, 7.4, MAIN + 1)],
    calls=[{"acks": [(0, 1, 8)] * 3, "rows": 24},
           {"acks": [(0, 1, 8)] * 2, "rows": 16}])

CASES = {
    "sync_ms.probe": (PROBE, (0.004 + 0.001) * 1e3 / 3),
    "pool_ms.probe": (PROBE, (0.001 + 0.002) * 1e3 / 3),
    "launch_ms.probe": (PROBE, (0.1 + 0.1) * 1e3 / 3),
    "syncs_per_query.probe": (PROBE, 96 / 32),
    "wal_ms.ingest": (INGEST, (0.2 + 0.1) * 1e3 / 5),
    "fsync_ms.ingest": (INGEST, (0.1 + 0.05 + 0.1) * 1e3 / 5),
    "fetch_ms.ingest": (INGEST, (0.3 + 0.1) * 1e3 / 2),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_value_and_silence(name):
    read = harness.reader(name)
    view, want = CASES[name]
    assert read(view) == pytest.approx(want)
    other = INGEST if view is PROBE else PROBE
    assert read(other) is None                  # another cell's kind
    build = dataclasses.replace(view, kind="build")
    assert read(build) is None
    # a program without these spans and counters (the parent's)
    bare = dataclasses.replace(view, spans=[], counters={})
    assert read(bare) is None
    if not name.startswith("syncs_"):
        assert read(dataclasses.replace(view, spans=None)) is None


def test_new_readers_are_listed_in_exactly_their_cells():
    for name, (view, _) in CASES.items():
        entry, = [m for m in harness.load_bench()["per_layer"]
                  if m["name"] == name]
        want = (["rand4m-exact-b16", "rand4m-approx-q1"]
                if view is PROBE else ["stream1m-ingest"])
        assert entry["workloads"] == want
