"""The systems under test, one file for each configuration ``kind``.

``systems/<kind>.py`` serves every configuration whose ``"kind"`` is
``<kind>``, and gives:

* ``system(config)``: the system under test, the program's entry points
  that the kind's loops call;
* ``control(config, traffic)``: the plain reference (or the program with
  its data) one precision below what the configuration states, put in
  the program's place, chosen by ``traffic["loop"]`` where the kind runs
  more than one loop;
* ``cpu_size(config, traffic)``: copies of both, cut to a size that a
  test on the CPU runs in seconds.

These files and ``system.py`` are the benchmark's only modules that
import the program (``src/repro``).  A new kind is a new file.
"""
from __future__ import annotations

import importlib
from pathlib import Path
from typing import List

HERE = Path(__file__).resolve().parent


def found() -> List[str]:
    """The kinds that have a file here."""
    return sorted(f.stem for f in HERE.glob("*.py")
                  if not f.stem.startswith("_"))


def of(kind: str):
    """The module of ``systems/<kind>.py``."""
    if kind not in found():
        raise SystemExit(f"unknown configuration kind {kind!r}; found: "
                         f"{found()}")
    return importlib.import_module(f"{__name__}.{kind}")
