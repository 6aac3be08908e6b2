"""Spans and counters that name the host's time: the probe path's
device reads (``exec.sync``, ``SearchStats.device_syncs``), program
launches (``exec.launch``) and pool updates (``exec.pool``); the write
path's ``insert`` / ``wal.*`` / ``fsync`` / ``segment.*`` /
``manifest.commit`` spans with the ``io.fsyncs`` counter; and the
mirror of every span into a running ``jax.profiler`` capture.
"""
import collections
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import summarization as S
from repro.core import tree as T
from repro.core.lsm import CoconutLSM
from repro.obs import (disable_tracing, enable_tracing, get_registry,
                       get_tracer)
from repro.obs.validate import validate
from repro.storage.store import SegmentStore

CFG = S.SummaryConfig(series_len=64, segments=8, bits=4)
STAGES = {"seed", "scan", "verify", "plan"}


@pytest.fixture
def obs():
    get_registry().reset()
    disable_tracing()
    get_tracer().clear()
    yield get_registry()
    get_registry().reset()
    disable_tracing()
    get_tracer().clear()


def _data(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, CFG.series_len)).astype(np.float32)


def _ancestors(s, by_id):
    while s["parent"]:
        s = by_id[s["parent"]]
        yield s


def _counter(name):
    return get_registry().snapshot().get(name, 0)


@pytest.fixture(scope="module")
def small_tree():
    return T.build(jnp.asarray(_data(4096)), CFG, leaf_size=64)


@pytest.mark.parametrize("traced", [True, False], ids=["traced", "untraced"])
@pytest.mark.parametrize("budget", [None, 6], ids=["exact", "budgeted"])
def test_probe_device_reads_are_counted_and_spanned(obs, small_tree,
                                                    budget, traced):
    queries = _data(4096)[:4] + np.float32(0.01)
    # warm: the first search of a tree also reads its fences
    T.exact_search_batch(small_tree, queries, k=3, budget=budget)
    syncs0 = _counter("query.device_syncs_total")
    bytes0 = _counter("query.d2h_bytes_total")
    if traced:
        enable_tracing()
    d, ids, st = T.exact_search_batch(small_tree, queries, k=3,
                                      budget=budget)
    disable_tracing()
    # paas, 4 planner reads, seed window and distances, and at least one
    # leaf group's bound
    assert st.device_syncs >= 8 and st.d2h_bytes > 0
    assert _counter("query.device_syncs_total") - syncs0 == st.device_syncs
    assert _counter("query.d2h_bytes_total") - bytes0 == st.d2h_bytes
    spans = get_tracer().spans()
    if not traced:
        assert spans == []
        return
    by_id = {s["id"]: s for s in spans}
    syncs = [s for s in spans if s["name"] == "exec.sync"]
    host = [s for s in spans if s["name"] in ("exec.pool", "exec.launch")]
    assert len(syncs) == st.device_syncs
    assert sum(s["args"]["bytes"] for s in syncs) == st.d2h_bytes
    assert {s["name"] for s in host} == {"exec.pool", "exec.launch"}
    for s in syncs + host:
        assert STAGES & {a["name"] for a in _ancestors(s, by_id)}, s
    # the counts do not depend on whether tracing is on
    d2, ids2, st2 = T.exact_search_batch(small_tree, queries, k=3,
                                         budget=budget)
    assert st2.device_syncs == st.device_syncs
    np.testing.assert_array_equal(d2, d)
    np.testing.assert_array_equal(ids2, ids)


def _ingest(tmp_path, policy, batches=8, rows=256):
    """A synchronous LSM over a segment store, traced while it takes
    ``batches`` inserts, a flush and a commit every second one."""
    raw = _data(batches * rows, seed=1)
    eng = CoconutLSM(CFG, buffer_capacity=2 * rows, leaf_size=64,
                     store=SegmentStore(str(tmp_path / "store")),
                     wal_fsync=policy)
    fsyncs0 = _counter("io.fsyncs")
    enable_tracing()
    for s in range(0, len(raw), rows):
        eng.insert(raw[s:s + rows])
    disable_tracing()
    fsyncs = _counter("io.fsyncs") - fsyncs0
    eng.close()
    return get_tracer().spans(), fsyncs


@pytest.mark.parametrize("policy,per_append", [
    ("always", 1), ("commit", 0), ("never", 0)])
def test_insert_and_commit_child_spans(obs, tmp_path, policy, per_append):
    spans, fsyncs = _ingest(tmp_path, policy)
    by_id = {s["id"]: s for s in spans}
    kids = collections.defaultdict(list)
    for s in spans:
        kids[s["parent"]].append(s)
    inserts = [s for s in spans if s["name"] == "insert"]
    assert len(inserts) == 8
    for ins in inserts:
        appends = [c for c in kids[ins["id"]] if c["name"] == "wal.append"]
        assert len(appends) == 1
        names = sorted(c["name"] for c in kids[appends[0]["id"]])
        assert names == sorted(["wal.encode", "wal.write"]
                               + ["fsync"] * per_append)
    commits = [s for s in spans if s["name"] == "compact.commit"]
    assert len(commits) == 4
    for c in commits:
        under = {s["name"] for s in spans
                 if c in _ancestors(s, by_id)}
        assert {"segment.fetch", "segment.write", "manifest.commit",
                "wal.rotate", "fsync"} <= under
    synced = [s for s in spans if s["name"] == "fsync"]
    assert fsyncs == len(synced)
    assert {s["args"]["file"] for s in synced} <= {"wal", "segment",
                                                   "manifest", "dir"}
    # a segment is synced once, a manifest once with its directory
    per_file = collections.Counter(s["args"]["file"] for s in synced)
    assert per_file["segment"] == per_file["manifest"] == len(commits)


@pytest.mark.timeout(120)
def test_spans_mirror_into_the_profiler_trace(obs, small_tree, tmp_path):
    from jax.profiler import ProfileData
    queries = _data(4096)[:2]
    T.exact_search_batch(small_tree, queries, k=2)
    enable_tracing()
    with jax.profiler.trace(str(tmp_path / "prof")):
        T.exact_search_batch(small_tree, queries, k=2, budget=4)
        _ingest(tmp_path, "always", batches=3)
    disable_tracing()
    ring = collections.Counter(s["name"] for s in get_tracer().spans())
    assert {"exec.sync", "exec.pool", "wal.append", "fsync",
            "segment.fetch"} <= set(ring)
    path, = glob.glob(os.path.join(str(tmp_path / "prof"), "**",
                                   "*.xplane.pb"), recursive=True)
    seen = collections.Counter(
        ev.name for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:") for line in plane.lines
        for ev in line.events if ev.name in ring)
    assert seen == ring


def test_validator_accepts_the_new_spans(obs, tmp_path):
    raw = _data(2048, seed=2)
    eng = CoconutLSM(CFG, buffer_capacity=512, leaf_size=64,
                     store=SegmentStore(str(tmp_path / "store")),
                     wal_fsync="always")
    enable_tracing()
    for s in range(0, len(raw), 256):
        eng.insert(raw[s:s + 256])
    eng.search_exact_batch(raw[:3], k=2)
    eng.search_exact_batch(raw[:3], k=2, budget=3, mode="approx")
    disable_tracing()
    eng.close()
    doc = get_tracer().export_chrome()
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"insert", "wal.append", "fsync", "compact.commit",
            "segment.write", "manifest.commit", "wal.rotate", "probe",
            "exec.sync", "exec.pool"} <= names
    assert validate(doc) == []
