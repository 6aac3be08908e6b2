"""Tests of the chip benchmark that need no chip: the trace reduction
and roofline arithmetic, the rules ``BENCHMARK.json`` has to keep, and
every traffic mix's loop and check at a tiny size on the CPU, with the
program, with the control in its place, and with faults planted under
the timed path.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent.parent
ROOT = HERE.parent.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import devtrace  # noqa: E402
import harness  # noqa: E402
import roofline  # noqa: E402
import systems  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


# ------------------------------------------------------ trace reduction
def test_interval_union_gaps_and_self_time():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (5.0, 5.0)]
    assert devtrace.merge(iv) == [(0.0, 2.0), (3.0, 4.0)]
    assert devtrace.length(iv) == pytest.approx(3.0)
    assert devtrace.gaps(iv, -1.0, 4.5) == [(-1.0, 0.0), (2.0, 3.0),
                                            (4.0, 4.5)]
    assert devtrace.gaps([], 0.0, 1.0) == [(0.0, 1.0)]
    # a layer's self time: its spans less its children's inside them
    outer = [(0.0, 10.0), (20.0, 30.0)]
    inner = [(2.0, 4.0), (25.0, 40.0), (50.0, 60.0)]
    assert devtrace.minus(outer, inner) == pytest.approx(20.0 - 2 - 5)


def test_busy_idle_and_op_time_inside_the_window():
    ops = [("bound", 1.0, 2.0), ("verify", 1.5, 3.0), ("bound", 8.0, 12.0)]
    assert devtrace.busy(ops, 0.0, 10.0) == pytest.approx(4.0)
    assert devtrace.op_time(ops, 0.0, 10.0) == [["bound", 3.0],
                                                ["verify", 1.5]]
    planes = {"a": ops, "b": [("x", 0.0, 10.0)], "c": []}
    assert devtrace.mean_busy(planes, 0.0, 10.0) == pytest.approx(7.0)


def test_device_lag_from_program_done_events():
    mods = [("p", 0.0, 1.0), ("p", 2.0, 3.0), ("p", 5.0, 5.5)]
    assert devtrace.device_lag(mods, [1.2, 3.25, 5.7]) == pytest.approx(
        0.2)
    assert devtrace.device_lag(mods, [1.2]) is None
    assert devtrace.shifted({"a": mods}, {"a": 0.5})["a"][0] == (
        "p", 0.5, 1.5)


def _profile(path, planes, runs, lags, device_stat=False):
    """A recorded trace of ``planes`` device planes, each running
    ``runs`` programs of 2 ms (plane ``j`` 0.3 ms behind plane 0), whose
    clock runs ``lags[j]`` seconds behind the host's.  The host records
    a marker at 1 ms and a program-done event for each program, naming
    its device by ``core_id`` where ``device_stat``.  Returns the
    ``perf_counter`` reading of the marker."""
    from jax.profiler import ProfileData
    ps = 1000                                # picoseconds a nanosecond
    start = [[(10_000_000 + 5_000_000 * i + 300_000 * j)
              for i in range(runs)] for j in range(planes)]
    host = ['events { metadata_id: 1 offset_ps: %d duration_ps: %d }'
            % (1_000_000 * ps, 1000 * ps)]
    for j in range(planes):
        for s in start[j]:
            ns = s + 2_000_000 + round(lags[j] * 1e9)
            stat = ('stats { metadata_id: 1 int64_value: %d }' % j
                    if device_stat else '')
            host.append('events { metadata_id: 2 offset_ps: %d '
                        'duration_ps: %d %s }' % (ns * ps, 1000 * ps, stat))
    txt = ['planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python"'
           ' timestamp_ns: 0 %s }' % " ".join(host),
           'event_metadata { key: 1 value { id: 1 name: "bench.mark" } }',
           'event_metadata { key: 2 value { id: 2 name: "%s" } }'
           % devtrace.DONE_EVENT,
           'stat_metadata { key: 1 value { id: 1 name: "%s" } } }'
           % devtrace.DONE_DEVICE_STAT]
    for j in range(planes):
        evs = " ".join('events { metadata_id: 1 offset_ps: %d '
                       'duration_ps: %d }' % (s * ps, 2_000_000 * ps)
                       for s in start[j])
        txt.append(
            'planes { id: %d name: "%s%d" lines { id: 1 name: "%s" '
            'timestamp_ns: 0 %s } lines { id: 2 name: "%s" timestamp_ns: 0'
            ' %s } event_metadata { key: 1 value { id: 1 name: '
            '"jit_step(1)" } } }' % (j + 2, devtrace.DEVICE_PLANE_PREFIX, j,
                                     devtrace.MODULES_LINE, evs,
                                     devtrace.OPS_LINE, evs))
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        "\n".join(txt)))
    return 100.0


@pytest.mark.parametrize("planes,device_stat", [
    (1, False), (1, True), (4, False), (4, True)])
def test_device_planes_put_on_the_host_clock(tmp_path, planes,
                                             device_stat):
    """A synthetic profile whose device clocks run a known lag behind the
    host's: one plane keeps the lag of :func:`devtrace.device_lag` bit
    for bit; several get a lag each where the done events name their
    device, and one shared lag where they do not."""
    lags = ([0.0013, 0.0021, 0.0008, 0.0017][:planes] if device_stat
            else [0.0013] * planes)
    path = tmp_path / "t.xplane.pb"
    mark = _profile(path, planes, 6, lags, device_stat)
    prof = devtrace.read_profile(str(path), "bench.mark", mark)
    mods = prof["modules"]
    assert len(mods) == planes and all(len(m) == 6 for m in mods.values())
    assert bool(prof["done_by"]) == device_stat
    got = devtrace.plane_lags(mods, prof["done"], prof["done_by"])
    names = [f"{devtrace.DEVICE_PLANE_PREFIX}{j}" for j in range(planes)]
    assert sorted(got) == names
    if planes == 1:
        assert got[names[0]] == devtrace.device_lag(mods[names[0]],
                                                   prof["done"])
    if not device_stat:                      # one lag, shared by all
        assert len(set(got.values())) == 1
    for name, lag in zip(names, lags):
        assert got[name] == pytest.approx(lag, abs=1e-9)
    ops = devtrace.shifted(prof["ops"], got)
    for name in names:
        for (_, s, e), (_, s0, e0) in zip(ops[name], prof["ops"][name]):
            assert (s - s0, e - e0) == pytest.approx((got[name],
                                                      got[name]))
    ends = sorted(e for m in devtrace.shifted(mods, got).values()
                  for _, _, e in m)           # every end now on its done
    assert ends == pytest.approx(prof["done"], abs=1e-9)


def test_no_lag_without_a_done_event_for_each_program():
    mods = {"a": [("p", 0.0, 1.0), ("p", 2.0, 3.0)],
            "b": [("p", 0.5, 1.5)]}
    assert devtrace.plane_lags(mods, [1.2], {}) is None
    assert devtrace.plane_lags({"a": []}, [1.2], {}) is None
    assert devtrace.plane_lags({"a": mods["a"]}, [1.2], {}) is None


def test_idle_gaps_named_by_the_innermost_host_span():
    ops = [("k", 0.0, 1.0), ("k", 2.0, 3.0), ("k", 6.0, 7.0),
           ("k", 9.0, 10.0)]
    host = [("call", 0.0, 8.0), ("verify", 1.0, 2.5),
            ("plan", 3.0, 5.0), ("compact.merge", 4.0, 9.5)]
    # gaps: [1,2] verify; [3,6] mid 4.5 -> plan is shortest over it;
    # [7,9] mid 8 -> only the merge covers it; [10,11] nothing
    got = dict(map(tuple, devtrace.idle_by_host(ops, host, 0.0, 11.0)))
    assert got == pytest.approx({"verify": 1.0, "plan": 3.0,
                                 "compact.merge": 2.0, "(no span)": 1.0})


def test_profile_read_back_on_the_benchmark_clock(tmp_path, monkeypatch):
    """A recorded trace: the host annotations, read as if they were a
    device's operations, land where ``perf_counter`` saw them."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    seen = []
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.mark"):
            mark = time.perf_counter()
        for _ in range(3):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("op"):
                time.sleep(0.02)
            seen.append((t0, time.perf_counter()))
    path = devtrace.find_xplane(str(tmp_path))
    prof = devtrace.read_profile(path, "bench.mark", mark)
    assert prof["ops"] == {}              # no device plane on the CPU
    monkeypatch.setattr(devtrace, "DEVICE_PLANE_PREFIX", "/host:CPU")
    monkeypatch.setattr(devtrace, "OPS_LINE", "python")
    prof = devtrace.read_profile(path, "bench.mark", mark)
    ops = [o for o in prof["ops"]["/host:CPU"] if o[0] == "op"]
    assert len(ops) == 3
    for (_, s, e), (a, b) in zip(ops, seen):
        assert abs(s - a) < 2e-3 and abs(e - b) < 2e-3
    with pytest.raises(ValueError):
        devtrace.read_profile(path, "no such marker", mark)


def test_roofline_bytes_and_peaks():
    # 3 leaves of 2000 rows, 16 one-byte codes each; 100 rows of 256 f32
    assert roofline.scan_bytes(3, 100, leaf_size=2000, segments=16,
                               series_len=256, rows=10 ** 6) == \
        3 * 2000 * 16 + 100 * 256 * 4
    # the last leaf is short: never more rows than the collection has
    assert roofline.scan_bytes(3, 0, leaf_size=2000, segments=16,
                               series_len=256, rows=4500) == 4500 * 16
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_merge_time_from_the_device_programs_in_the_window():
    progs = {"/device:TPU:0": [
        ("jit__merge_cols(123)", 1.0, 1.5), ("jit_summarize(7)", 2.0, 3.0),
        ("jit__merge_cols(456)", 4.0, 4.1),
        ("jit__merge_cols(123)", 9.5, 11.0)]}      # ends past the window
    view = harness.RunView(kind="ingest", config={}, traffic={}, calls=[],
                           window=(0.5, 10.0), setup_s=1.0, extra={},
                           counters={}, device_programs=progs)
    assert view.program_times("jit__merge_cols") == pytest.approx(
        [0.5, 0.1])
    assert harness.reader("merge_ms.ingest")(view) == pytest.approx(300.0)
    assert harness.reader("merge_ms.ingest")(
        dataclasses.replace(view, device_programs={})) is None
    assert harness.reader("merge_ms.ingest")(
        dataclasses.replace(view, device_programs=None)) is None


# ---------------------------------------------------- BENCHMARK.json
def test_benchmark_json_keys_names_and_units():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for group, want in keys.items():
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        for e in BENCH[group]:
            assert set(e) - {"workloads"} == want, e["name"]
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher"), e["name"]
            for text in ("why", "layer", "source"):
                if text in e:
                    assert 1 <= len(e[text]) <= 200 and "\n" not in \
                        e[text] and "\t" not in e[text]
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    chips = [w["chips"] for w in BENCH["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 2)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_name_resolves_to_a_file():
    import loops
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert c["file"].startswith("benchmarks/chip/configs/")
        assert set(c["reduced"]) <= set(cfg), c["name"]
        assert cfg["kind"] in systems.found()
        kind = systems.of(cfg["kind"])
        for part in ("system", "control", "cpu_size"):
            assert callable(getattr(kind, part)), (cfg["kind"], part)
    for w in BENCH["workloads"]:
        assert w["config"] in {c["name"] for c in BENCH["configs"]}
        traffic = json.loads((HERE / "traffic" /
                              f"{w['traffic']}.json").read_text())
        assert traffic["loop"] in loops.found()
        assert issubclass(loops.of(traffic["loop"]), loops.Loop)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(harness.reader(m["name"])), m["name"]


def test_unknown_kind_and_loop_name_what_is_found():
    import loops
    with pytest.raises(SystemExit, match="static_collection"):
        systems.of("no_such_kind")
    with pytest.raises(SystemExit, match="probe"):
        loops.of("no_such_loop")
    assert {"static_collection", "streaming_lsm"} <= set(systems.found())
    assert {"probe", "ingest", "build"} <= set(loops.found())


def test_each_cell_reports_what_its_metrics_need():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert reports(e2e[m["moves"]], cell), (m["name"], cell)
    for cell in cells:
        got = [n for n, m in e2e.items() if reports(m, cell)]
        assert "setup_s" in got and len(got) >= 2, cell
        assert any(reports(m, cell) for m in BENCH["per_layer"]), cell


# ------------------------------------------------- loops on the CPU
_CELL_OF = harness.cell_of


def tiny(workload):
    """The cell's configuration and mix at a size a CPU test holds, as
    the configuration kind's file cuts them."""
    cell, entry, config, traffic = _CELL_OF(BENCH, workload)
    config, traffic = systems.of(config["kind"]).cpu_size(config, traffic)
    return cell, entry, config, traffic


@pytest.fixture
def tiny_cells(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "cell_of", lambda b, w: tiny(w))
    monkeypatch.setattr(harness, "WORK", tmp_path / "work")


def run(workload, system=None, trace=False, seed=2 ** 33 + 5):
    out, info = harness.run_cell(BENCH, workload, seed, 0.3, trace,
                                 time.perf_counter(), system=system)
    assert json.loads(json.dumps(out)) == out
    return out, info


CELLS = [w["name"] for w in BENCH["workloads"]]
STATIC = systems.of("static_collection")
STREAM = systems.of("streaming_lsm")


@pytest.mark.parametrize("workload", CELLS)
def test_loop_and_check_pass_on_the_program(tiny_cells, workload):
    out, info = run(workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "checks"
    want = {m["name"] for m in harness.metrics_of(BENCH, workload, False)}
    assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert m["value"] > 0
    if workload == "rand4m-approx-q1":
        assert 0 < out["metrics"]["recall_at_10"]["value"] <= 1


def test_probe_seeds_run_one_pool_in_another_order(tmp_path):
    """With a ``data_key`` every seed probes the same collection with the
    same queries, in an order of its own."""
    import loops
    _, _, config, traffic = tiny("rand4m-approx-q1")
    pools = []
    for seed in (1, 2 ** 40 + 1):
        lp = loops.of("probe")(config, traffic, seed,
                               STATIC.StaticIndex(config),
                               workdir=tmp_path)
        lp.setup()
        pools.append(lp.pool.reshape(-1, lp.pool.shape[-1]))
    a, b = pools
    assert not np.array_equal(a, b)
    key = lambda x: x[np.lexsort(x.T[::-1])]
    np.testing.assert_array_equal(key(a), key(b))


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_the_check(tiny_cells, workload):
    _, _, config, traffic = tiny(workload)
    out, _ = run(workload, systems.of(config["kind"]).control(config,
                                                              traffic))
    assert out["failed"] == 0            # refused by the check, not a crash
    assert not out["correct"], out["checks"]


# --------------------------------------------------- planted faults


class HalfBatch(STATIC.StaticIndex):
    """Answers the first half of the batch and copies it over the rest."""

    def search(self, tree, queries, *, k, budget):
        h = max(1, len(queries) // 2)
        d, ids, c, gap = super().search(tree, queries[:h], k=k,
                                        budget=budget)
        rep = -(-len(queries) // h)
        cut = lambda a: None if a is None else np.concatenate(
            [a] * rep)[:len(queries)]
        return cut(d), cut(ids), c, cut(gap)


class AlteredAnswer(STATIC.StaticIndex):
    def search(self, tree, queries, *, k, budget):
        d, ids, c, gap = super().search(tree, queries, k=k, budget=budget)
        ids = ids.copy()
        ids[0, -1] = (ids[0, -1] + 1) % tree.n
        return d, ids, c, gap


class Unchanged(STATIC.StaticIndex):
    """A search that returns its pool as it started: nothing found."""

    def search(self, tree, queries, *, k, budget):
        d, ids, c, gap = super().search(tree, queries, k=k, budget=budget)
        return (np.full_like(d, np.inf), np.full_like(ids, -1), c,
                None if gap is None else np.zeros_like(gap))


class UnsortedBuild(STATIC.StaticIndex):
    """A build that leaves the rows in their arrival order."""

    def build(self, raw):
        import jax.numpy as jnp
        tree = super().build(raw)
        back = jnp.argsort(tree.offsets)
        return dataclasses.replace(
            tree, keys=tree.keys[back], codes=tree.codes[back],
            paas=tree.paas[back], raw=raw,
            offsets=jnp.arange(tree.n, dtype=jnp.int32))


class AlteredRow(STATIC.StaticIndex):
    def build(self, raw):
        tree = super().build(raw)
        return dataclasses.replace(tree, raw=tree.raw.at[3, 7].add(1e-3))


class _Writer:
    def __init__(self, eng, rows=lambda r: r, drop=False):
        self.eng, self.rows, self.drop = eng, rows, drop

    def insert(self, rows):
        if not self.drop:
            self.eng.insert(self.rows(rows))

    def __getattr__(self, name):
        return getattr(self.eng, name)


class DroppedInserts(STREAM.StreamIndex):
    """Acknowledges every insert and keeps none: the state unchanged."""

    def create(self, root):
        return _Writer(super().create(root), drop=True)


class AlteredRows(STREAM.StreamIndex):
    def create(self, root):
        def alter(r):
            r = np.array(r)
            r[:, 0] += 1e-3
            return r
        return _Writer(super().create(root), rows=alter)


class UnloggedInserts(STREAM.StreamIndex):
    """Acknowledges inserts that never reach the write-ahead log."""

    def create(self, root):
        eng = super().create(root)
        if eng.wal is not None:
            eng.wal.append = lambda *a, **kw: 0
        return eng


FAULTS = {
    "rand4m-exact-b16": [HalfBatch, AlteredAnswer, Unchanged],
    "rand4m-approx-q1": [AlteredAnswer, Unchanged],
    "rand4m-build": [UnsortedBuild, AlteredRow],
    "stream1m-ingest": [DroppedInserts, AlteredRows, UnloggedInserts],
}


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w, fs in FAULTS.items() for f in fs],
    ids=lambda x: getattr(x, "__name__", x))
def test_planted_fault_fails_the_check(tiny_cells, workload, fault):
    _, _, config, _ = tiny(workload)
    out, _ = run(workload, fault(config))
    assert out["failed"] == 0            # refused by the check, not a crash
    assert not out["correct"], out["checks"]


# ------------------------------------------------------ the command
def _command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_the_command_refuses_the_cpu():
    p = _command(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr and p.stdout.strip() == ""


def test_the_command_fails_without_the_program(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
