"""One run of one cell: set-up, measured window, check, result line.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration (``configs/<config>.json``), whose ``"kind"`` names
the system under test and its control (``systems/<kind>.py``); its
traffic mix (``traffic/<mix>.json``), whose ``"loop"`` names the loop
that runs it (``loops/<loop>.py``); and every metric it reports
(``metrics/<metric>.py``, a ``read(run)`` that returns a number or None
when it finds nothing to read).  Adding a cell, a mix, a metric, a
configuration kind or a loop therefore means adding files and entries,
not editing these.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import re
import shutil
import sys
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent              # the checkout
WORK = ROOT / ".bench_work"            # scratch of a run, removed after
MARKER = "bench.window_start"


def load_bench(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_of(bench: dict, workload: str):
    """(cell, configuration entry, configuration, traffic) by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, entry, config, traffic


def metrics_of(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics a cell reports: its end-to-end metrics in a run
    without the trace, its per-layer metrics in a traced run."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def reader(name: str):
    """``read`` of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class RunView:
    """What a metric reader sees of one run.  Times are seconds on
    ``time.perf_counter``'s clock."""
    kind: str                       # the loop's kind: probe, ingest, build
    config: dict
    traffic: dict
    calls: List[dict]               # one record per call of the window
    window: tuple                   # (start, end)
    setup_s: float
    extra: Dict[str, float]         # numbers the check computed
    counters: Dict[str, float]      # registry deltas over the window
    spans: Optional[List[tuple]] = None    # (name, start, end, thread)
    device_ops: Optional[dict] = None      # plane -> [(name, start, end)]
    device_programs: Optional[dict] = None  # the same, of whole programs
    peaks: Optional[Dict[str, float]] = None
    main_thread: int = 0

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def spans_named(self, names) -> List[tuple]:
        """``(start, end)`` of the main thread's spans named ``names``
        inside the window."""
        lo, hi = self.window
        return [(s, e) for n, s, e, tid in (self.spans or ())
                if n in names and s >= lo and e <= hi
                and tid == self.main_thread]

    def program_times(self, name: str) -> List[float]:
        """Device seconds of each run of the program ``name`` (without
        the hash the compiler appends) inside the window."""
        lo, hi = self.window
        return [e - s for p in (self.device_programs or {}).values()
                for n, s, e in p
                if re.sub(r"\(\d+\)$", "", n) == name and s >= lo
                and e <= hi]

    def busy_s(self) -> Optional[float]:
        if self.device_ops is None:
            return None
        import devtrace
        return devtrace.mean_busy(self.device_ops, *self.window)


def _counter_delta(before: dict, after: dict) -> Dict[str, float]:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if isinstance(v, (int, float))}


class Annotator:
    """Opens a profiler annotation around a call into a layer and keeps
    its ``(name, start, end)`` on ``perf_counter``'s clock."""

    def __init__(self):
        self.spans: List[tuple] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.spans.append((name, t0, time.perf_counter(), -1))


@contextlib.contextmanager
def _profiled(trace_dir: Path):
    """The JAX profiler over the window: host annotations and device
    operations, without the Python function tracer."""
    import jax
    from jax.profiler import ProfileOptions
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        yield


def _traced_window(loop, seconds: float):
    """The window under the profiler, with the program's spans on.
    Returns (host spans as ``(name, start, end, thread)``, device
    operations and programs by plane, the device clock's lag behind the
    host's where one plane ran programs (else None), and what the
    profile held, each plane's lag with it), all on ``perf_counter``'s
    clock."""
    import jax
    import devtrace
    import system as sysmod
    trace_dir = WORK / "trace"
    sysmod.enable_spans()
    ann = Annotator()
    with _profiled(trace_dir):
        with jax.profiler.TraceAnnotation(MARKER):
            mark = time.perf_counter()
        loop.window(seconds, ann)
    raw_spans, epoch, dropped = sysmod.take_spans()
    if dropped:
        raise RuntimeError(f"the span ring dropped {dropped} spans")
    prof = devtrace.read_profile(devtrace.find_xplane(str(trace_dir)),
                                 MARKER, mark)
    shutil.rmtree(trace_dir, ignore_errors=True)
    ops, mods = prof["ops"], prof["modules"]
    if not any(ops.values()):
        raise RuntimeError(f"no device operations in the trace; planes: "
                           f"{prof['planes']}")
    # the programs' ends line up with the host's program-done events,
    # which puts each device plane on the host's clock
    lags = devtrace.plane_lags(mods, prof["done"], prof["done_by"])
    lag = None
    if lags is not None:
        ops, mods = devtrace.shifted(ops, lags), devtrace.shifted(mods, lags)
        if len(lags) == 1:
            lag, = lags.values()
    held = {"programs": {p: len(m) for p, m in mods.items()},
            "done": len(prof["done"]),
            "done_by": {p: len(v) for p, v in prof["done_by"].items()},
            "done_stats": prof["done_stats"], "lags": lags}
    spans = [(s["name"], epoch + s["ts"] * 1e-6,
              epoch + (s["ts"] + s["dur"]) * 1e-6, s["tid"])
             for s in raw_spans]
    return spans + ann.spans, ops, mods, lag, held


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, system=None, clock=None,
             device_kind: Optional[str] = None) -> Tuple[dict, dict]:
    """Set up, measure, check and reduce one run of ``workload``.
    Returns (the result object, facts about the run for the log);
    prints nothing.  ``system`` puts something in the program's place
    (the control, a planted fault); ``device_kind`` names the peaks the
    roofline readers use."""
    import jax
    import loops
    import system as sysmod
    import systems
    _, _, config, traffic = cell_of(bench, workload)
    if system is None:
        system = systems.of(config["kind"]).system(config)
    workdir = WORK / workload
    shutil.rmtree(workdir, ignore_errors=True)
    loop = loops.of(traffic["loop"])(config, traffic, seed, system,
                                     workdir=workdir)
    kind = loop.kind or traffic["loop"]
    try:
        loop.setup()
        setup_s = time.perf_counter() - t_start
        before = sysmod.registry_snapshot()
        c0 = clock.read() if clock is not None else (0.0, 0, 0)
        spans = ops = mods = lag = held = None
        if trace:
            spans, ops, mods, lag, held = _traced_window(loop, seconds)
        else:
            loop.window(seconds)
        c1 = clock.read() if clock is not None else (0.0, 0, 0)
        counters = _counter_delta(before, sysmod.registry_snapshot())
        from chipenv import peak_bytes
        peak = peak_bytes()
        loop.release()
        t_check = time.perf_counter()
        checked = loop.check()
        check_s = time.perf_counter() - t_check
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    from roofline import peaks as peak_table
    view = RunView(kind=kind, config=config, traffic=traffic,
                   calls=loop.calls, window=loop.window_bounds,
                   setup_s=setup_s, extra=checked["extra"],
                   counters=counters, spans=spans, device_ops=ops,
                   device_programs=mods,
                   peaks=None if device_kind is None
                   else peak_table(device_kind),
                   # the thread id as the program's spans record it
                   main_thread=threading.get_ident() & 0x7FFFFFFF)
    metrics = {}
    for m in metrics_of(bench, workload, trace):
        v = reader(m["name"])(view)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = checked["checks"]
    correct = loop.failed == 0 and all(c["value"] <= c["limit"]
                                       for c in checks.values())
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": loop.attempted,
           "failed": loop.failed, "metrics": metrics, "device": device}
    info = {"window_s": view.window_s, "window_compiles": c1[1] - c0[1],
            "window_compile_s": c1[0] - c0[0], "calls": loop.attempted,
            "errors": loop.errors[:3], "setup": loop.phases,
            "check_s": check_s, "setup_compiles": c0[1],
            "setup_compile_s": c0[0], "cache_hits": c1[2]}
    info.update(loop.info(counters))
    if trace:
        import devtrace
        lo, hi = view.window
        device["busy_s"] = view.busy_s()
        device["window_s"] = view.window_s
        host = [(n, s, e) for n, s, e, _ in spans]
        allops = [o for p in ops.values() for o in p]
        # programs by name, without the hash jit appends
        progs = [(re.sub(r"\(\d+\)$", "", n), a, b)
                 for p in mods.values() for n, a, b in p]
        out["breakdown"] = {
            "device_ops": devtrace.op_time(progs or allops, lo, hi),
            "idle_gaps": devtrace.idle_by_host(allops, host, lo, hi)}
        info["device_lag_s"] = lag
        info["device_programs"] = len(progs)
        info["trace"] = held
    out["checks"] = checks
    return out, info


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(
        description="Run one cell of the chip benchmark once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_bench()
    cell, _, _, _ = cell_of(bench, args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    from chipenv import CompileClock, require_tpu, use_compile_cache
    use_compile_cache(ROOT / ".jax_cache")
    clock = CompileClock()
    dev = require_tpu(cell["chips"])
    from roofline import peaks
    peaks(dev["kind"])                 # an unknown chip is an error
    out, info = run_cell(bench, args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start, clock=clock,
                         device_kind=dev["kind"])
    print(f"info: {json.dumps(info)}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
