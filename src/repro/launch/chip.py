"""Set-up shared by every program that runs the index on the chip.

* :func:`use_compile_cache` places JAX's persistent compilation cache.
  Call it before the first compile: JAX fixes the cache at that point.
* :func:`require_tpu` names the device a run is on and refuses to run
  anywhere else — a number taken on the CPU is never a chip number.
* :class:`CompileClock` sums the seconds spent getting executables
  (compiling, or loading them from the cache), so a run can show what the
  cache saved it.

Importing this module touches no device state.
"""
from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import Dict, Union

import jax
from jax import monitoring

__all__ = ["CACHE_ENV", "use_compile_cache", "require_tpu",
           "CompileClock"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# jax records this around every backend compile, cache load included
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def use_compile_cache(default_dir: Union[str, Path]) -> str:
    """Place the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing
    else is set here.  Otherwise the cache goes to ``default_dir``, which
    must be a fixed path: the path is part of what a later run looks up.
    Every executable is cached, not only those slower than JAX's default
    threshold, so a warm run compiles nothing.
    """
    where = os.environ.get(CACHE_ENV)
    if not where:
        where = str(default_dir)
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


def require_tpu() -> Dict[str, object]:
    """The device this process runs on, as JAX reports it.  Raises
    ``SystemExit`` unless it is a TPU: there is no CPU fallback."""
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"no TPU: JAX reports {len(devs)} {dev.platform} device(s)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


class CompileClock:
    """Running totals of backend compiles, their seconds and
    persistent-cache hits, fed by JAX's monitoring events.  Install once
    per process."""

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            with self._lock:
                self.seconds += secs
                self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def read(self):
        """``(seconds, compiles, cache_hits)`` so far."""
        with self._lock:
            return self.seconds, self.compiles, self.cache_hits
