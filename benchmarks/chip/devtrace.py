"""Reduction of a profiler trace and of host spans to the numbers the
per-layer readers report: device busy time as the union of the
intervals in which an operation ran, idle gaps and what the host was
doing in each, and device time by operation.

Times are seconds on one clock.  The profiler's trace has its own
clock; :func:`read_profile` puts the device's operations on the
benchmark's clock (``time.perf_counter``) through one annotated marker
that the harness opens at a known ``perf_counter`` reading just before
the window.  Host spans (the program's ``obs`` spans, the benchmark's
own annotations) are on ``perf_counter`` already.
"""
from __future__ import annotations

import glob
import heapq
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

# the profiler's device planes and the line of each that holds the
# operations as they ran on the chip
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def merge(iv: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of intervals (empty ones dropped)."""
    out: List[list] = []
    for s, e in sorted((s, e) for s, e in iv if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(iv: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv
            if min(e, hi) > max(s, lo)]


def length(iv: Iterable[Interval]) -> float:
    """Length of the union of ``iv``."""
    return sum(e - s for s, e in merge(iv))


def gaps(iv: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that no interval of ``iv`` covers."""
    out, t = [], lo
    for s, e in merge(clip(iv, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def minus(a: Iterable[Interval], b: Iterable[Interval]) -> float:
    """Length of the union of ``a`` less the part of it that ``b``
    covers: a layer's self time, with ``a`` its spans and ``b`` its
    children's."""
    a = merge(a)
    return length(a) - length(
        iv for s, e in a for iv in clip(merge(b), s, e))


def busy(ops: Sequence[Tuple[str, float, float]], lo: float,
         hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which some operation ran."""
    return length(clip(((s, e) for _, s, e in ops), lo, hi))


def op_time(ops: Sequence[Tuple[str, float, float]], lo: float,
            hi: float, top: int = 10) -> List[list]:
    """``[name, seconds]`` of the operations that took the most device
    time in ``[lo, hi]``, most first."""
    tot: Dict[str, float] = defaultdict(float)
    for name, s, e in ops:
        for cs, ce in clip([(s, e)], lo, hi):
            tot[name] += ce - cs
    return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])
            [:top]]


def idle_by_host(ops: Sequence[Tuple[str, float, float]],
                 host: Sequence[Tuple[str, float, float]], lo: float,
                 hi: float, top: int = 10) -> List[list]:
    """Idle device time in ``[lo, hi]``, each gap named by the innermost
    (shortest) host span that covers its midpoint, summed by name;
    ``[name, seconds]``, most first.  A gap no span covers is
    ``"(no span)"``."""
    tot: Dict[str, float] = defaultdict(float)
    spans = sorted(host, key=lambda h: h[1])
    active: list = []                 # heap of (duration, end, name)
    j = 0
    for s, e in gaps(((a, b) for _, a, b in ops), lo, hi):
        mid = 0.5 * (s + e)
        while j < len(spans) and spans[j][1] <= mid:
            n, a, b = spans[j]
            heapq.heappush(active, (b - a, b, n))
            j += 1
        while active and active[0][1] < mid:     # ended before: never
            heapq.heappop(active)                # active again
        tot[active[0][2] if active else "(no span)"] += e - s
    return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])
            [:top]]


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    return files[-1]


MODULES_LINE = "XLA Modules"
# the host event the TPU runtime records when a device program is done,
# and its stat that names the device: core j is plane /device:TPU:j
DONE_EVENT = "tpu::System::Execute=>Done"
DONE_DEVICE_STAT = "core_id"


def read_profile(path: str, marker: str, marker_perf: float) -> dict:
    """A profiler trace on ``perf_counter``'s clock.

    ``marker`` is the name of a host annotation opened when
    ``perf_counter`` read ``marker_perf``; it maps the trace's host
    clock onto ``perf_counter``.  Returns ``ops`` and ``modules``, the
    operations and the programs of each device plane as ``(name, start,
    end)``; ``done``, the host's program-done events; ``done_by``, the
    same split by the device plane each names (empty unless every one
    names its device by :data:`DONE_DEVICE_STAT`);
    ``done_stats``, the names of the stats the done events carry; and
    ``planes``, every plane's name, for diagnosis."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    mark_ns: Optional[float] = None
    names = []
    ops: Dict[str, list] = {}
    mods: Dict[str, list] = {}
    done: List[float] = []
    done_dev: List[Optional[int]] = []
    done_stats = set()
    for plane in pd.planes:
        names.append(plane.name)
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                (ops if line.name == OPS_LINE else mods)[plane.name] = [
                    (ev.name, ev.start_ns, ev.end_ns) for ev in line.events]
            for ev in line.events:
                if mark_ns is None and ev.name == marker:
                    mark_ns = ev.start_ns
                elif ev.name == DONE_EVENT:
                    done.append(ev.start_ns)
                    stats = dict(ev.stats)
                    done_stats.update(stats)
                    dev = stats.get(DONE_DEVICE_STAT)
                    done_dev.append(None if dev is None else int(dev))
    if mark_ns is None:
        raise ValueError(f"marker {marker!r} not in the trace")
    off = marker_perf - mark_ns * 1e-9

    def conv(d):
        return {p: [(n, s * 1e-9 + off, e * 1e-9 + off) for n, s, e in v]
                for p, v in d.items()}
    done_by: Dict[str, List[float]] = defaultdict(list)
    if done and None not in done_dev:
        for t, dev in zip(done, done_dev):
            done_by[f"{DEVICE_PLANE_PREFIX}{dev}"].append(t * 1e-9 + off)
    return {"ops": conv(ops), "modules": conv(mods),
            "done": sorted(t * 1e-9 + off for t in done),
            "done_by": {p: sorted(v) for p, v in done_by.items()},
            "done_stats": sorted(done_stats), "planes": names}


def device_lag(modules: Sequence[Tuple[str, float, float]],
               done: Sequence[float]) -> Optional[float]:
    """Seconds by which the device's clock in the trace runs behind the
    host's: the median, over the programs in order, of the host's
    program-done event less the program's end on the device.  None
    unless there is one done event for each program."""
    if not modules or len(modules) != len(done):
        return None
    ends = sorted(e for _, _, e in modules)
    lags = sorted(d - e for d, e in zip(done, ends))
    return lags[len(lags) // 2]


def plane_lags(modules: Dict[str, Sequence[Tuple[str, float, float]]],
               done: Sequence[float],
               done_by: Optional[Dict[str, Sequence[float]]] = None
               ) -> Optional[Dict[str, float]]:
    """The lag (:func:`device_lag`) of each device plane that ran a
    program, by plane name, or None where it cannot be told.

    One plane: its programs against every done event.  Several: each
    plane against the done events that name its device, where every
    plane's can be split so; otherwise one lag shared by all planes,
    from every plane's programs together against every done event."""
    planes = {p: m for p, m in modules.items() if m}
    if len(planes) == 1:
        (p, m), = planes.items()
        lag = device_lag(m, done)
        return None if lag is None else {p: lag}
    if not planes:
        return None
    if done_by:
        each = {p: device_lag(m, done_by.get(p, ()))
                for p, m in planes.items()}
        if None not in each.values():
            return each
    lag = device_lag([e for m in planes.values() for e in m], done)
    return None if lag is None else dict.fromkeys(planes, lag)


def shifted(planes: Dict[str, List[Tuple[str, float, float]]],
            lags: Dict[str, float]
            ) -> Dict[str, List[Tuple[str, float, float]]]:
    """Every plane's intervals moved by its lag in ``lags`` (a plane
    without one, which ran no program, stays)."""
    return {p: [(n, s + lags.get(p, 0.0), e + lags.get(p, 0.0))
                for n, s, e in v]
            for p, v in planes.items()}


def mean_busy(planes: Dict[str, List[Tuple[str, float, float]]],
              lo: float, hi: float) -> float:
    """Busy seconds in ``[lo, hi]`` averaged over the device planes that
    ran anything (the chips a run used)."""
    b = [busy(ops, lo, hi) for ops in planes.values() if ops]
    return sum(b) / len(b) if b else 0.0
