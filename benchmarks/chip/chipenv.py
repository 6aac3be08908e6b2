"""Device set-up for the chip benchmark, kept here so that no change to
the program can move it.

* :func:`use_compile_cache` places JAX's persistent compilation cache at
  a fixed path (or where ``JAX_COMPILATION_CACHE_DIR`` says).
* :func:`require_tpu` names the device and refuses anything but enough
  TPU chips: a number taken on the CPU is never a chip number.
* :class:`CompileClock` counts backend compiles and cache loads, so a run
  can show that nothing compiled inside its measured window.
* :func:`key_from_seed` turns any whole seed, past 32 bits too, into a
  PRNG key (``PRNGKey`` alone keeps only the low 32 bits).

Copied from ``src/repro/launch/chip.py`` (same behaviour).  Importing
this module touches no device.
"""
from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import Dict, Union

import jax
from jax import monitoring

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# jax records this around every backend compile, cache load included
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def use_compile_cache(default_dir: Union[str, Path]) -> str:
    """Place the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it and nothing
    else is set here; otherwise the cache goes to ``default_dir``, a
    fixed path (the path is part of what a later run looks up).  Every
    executable is cached, so a warm run compiles nothing."""
    where = os.environ.get(CACHE_ENV)
    if not where:
        where = str(default_dir)
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


def require_tpu(chips: int = 1) -> Dict[str, object]:
    """The devices this process runs on, as JAX reports them.  Raises
    ``SystemExit`` (no result is printed) unless they are at least
    ``chips`` TPU chips."""
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"no TPU: JAX reports {len(devs)} {dev.platform} device(s)")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX reports "
                         f"{len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def peak_bytes() -> int:
    """Peak device bytes in use on the fullest local device."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


class CompileClock:
    """Running totals of backend compiles (cache loads included), their
    seconds, and persistent-cache hits, fed by JAX's monitoring events.
    Install once per process."""

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


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key that differs for every seed in ``[0, 2**64)``."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)
