"""The traffic loops of the benchmark, one file each.

A traffic mix is a data file (``traffic/<mix>.json``) whose ``"loop"``
names a file of this package, ``loops/<loop>.py``; the rest of the mix
is the loop's parameters.  A loop file gives ``LOOP``, a subclass of
:class:`Loop`, which :func:`of` finds by the file's name, so a new loop
is a new file.  Every loop is closed: one client that waits for each
reply before it sends the next call.  The system has no request queue,
so an open loop would have to invent the batching that belongs to the
program.

A loop runs in four steps, each called by the harness:

1. ``setup()``: make the cell's data on the device from the seed (or
   from the mix's fixed data key), build the program's state, and warm
   up every shape the window will use.
2. ``window(seconds, annotate)``: whole calls, back to back.  The window
   runs from the first call's start to the end of the first call that
   finishes ``seconds`` or more after it (for the probe mixes, the end of
   a whole pass through the query pool).
3. ``release()``: drop the program's state, once the device's peak
   memory has been read.
4. ``check()``: compare what the window's calls produced with the plain
   reference (:mod:`refknn`), and return each compared number beside its
   limit (from the mix's ``"limits"``).

A loop calls the program only through the system object it is given
(``systems/<kind>.py``), so the control and planted faults can take the
program's place.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

from chipenv import key_from_seed

# ids of the generators' keys, folded into the seed's key
_DATA, _POOL, _WARM, _CHECK, _SAMPLE, _ORDER = 1, 2, 3, 4, 5, 6


def _no_annotation(name: str):
    return contextlib.nullcontext()


class Loop:
    """State and records shared by every loop.

    ``kind`` names the records its calls keep, which the metric readers
    key on (``RunView.kind``; empty: the loop's own name).  A new loop
    whose call records hold what the probe loop's do (``queries``,
    ``counts``, ``d``, ``ids``) says ``"probe"`` and is read by the probe
    metrics."""

    kind = ""

    def __init__(self, config: dict, traffic: dict, seed: int, system,
                 workdir: Optional[Path] = None):
        self.config, self.traffic = config, traffic
        self.system = system
        self.workdir = workdir
        self.key = key_from_seed(seed)
        self.calls: List[dict] = []        # one record per call
        self.failed = 0
        self.errors: List[str] = []
        self.phases: Dict[str, float] = {}     # seconds of set-up steps
        self._t = time.perf_counter()

    def _phase(self, name: str) -> None:
        now = time.perf_counter()
        self.phases[name] = now - self._t
        self._t = now

    def _run_window(self, seconds: float, call: Callable[[int], dict],
                    annotate, done: Callable[[], bool] = lambda: True,
                    name: str = "bench.call") -> None:
        """Call ``call(i)`` for i = 0, 1, ... until a call ends at least
        ``seconds`` after the first began and ``done()`` holds.  A call
        that raises counts as failed; the window stops after 3 such."""
        t_start = None
        i = 0
        while True:
            t0 = time.perf_counter()
            if t_start is None:
                t_start = t0
            try:
                with annotate(name):
                    rec = call(i)
            except Exception as e:            # counted, reported, judged
                self.failed += 1
                self.errors.append(f"call {i}: {type(e).__name__}: {e}")
                rec = {"error": True}
            t1 = time.perf_counter()
            self.calls.append(dict(rec, t0=t0, t1=t1, i=i))
            i += 1
            if self.failed >= 3 or (t1 - t_start >= seconds and done()):
                break

    @property
    def window_bounds(self):
        return self.calls[0]["t0"], self.calls[-1]["t1"]

    @property
    def attempted(self) -> int:
        return len(self.calls)

    def release(self) -> None:
        gc.collect()

    def info(self, counters: Dict[str, float]) -> dict:
        """Facts about the run for the log, beside the harness's own;
        ``counters`` are the registry's deltas over the window."""
        return {}


def _limits(traffic: dict, values: Dict[str, float]) -> Dict[str, dict]:
    lim = traffic["limits"]
    return {n: {"value": v, "limit": lim[n]} for n, v in values.items()}


HERE = Path(__file__).resolve().parent


def found() -> List[str]:
    """The loops that have a file here."""
    return sorted(f.stem for f in HERE.glob("*.py")
                  if not f.stem.startswith("_"))


def of(name: str) -> type:
    """The loop class of ``loops/<name>.py``; only that file is
    imported, so one loop's imports never run in another's cell."""
    if name not in found():
        raise SystemExit(f"unknown traffic loop {name!r}; found: "
                         f"{found()}")
    return importlib.import_module(f"{__name__}.{name}").LOOP
