"""Backend compiles and persistent-cache hits seen by this process
(copied from `chip_smoke.py`; the original is listed for removal in
PERF.md).

JAX reports a backend compile for every executable that is not in the
process's own memory, including one served from the persistent cache,
so `compiles` counts every program this process had to load."""
from __future__ import annotations

import collections
import logging

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def totals(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.seconds,
                "cache_hits": self.hits, "cache_misses": self.misses}

    def since(self, before: dict) -> dict:
        now = self.totals()
        return {k: now[k] - before[k] for k in now}


class LoweredNames(logging.Handler):
    """Names of the programs JAX lowers while active: a program lowered
    inside the window is one that set-up did not warm.  Reads the debug
    record JAX writes for every lowering, without printing it."""

    LOGGER = "jax._src.interpreters.pxla"

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names: collections.Counter = collections.Counter()
        self._logger = logging.getLogger(self.LOGGER)
        self._saved = None

    def emit(self, record):
        if record.msg.startswith("Compiling %s with global shapes"):
            name, avals = record.args[0], record.args[1]
            shapes = ",".join(str(a.str_short()) for a in avals)
            self.names[f"{name}({shapes})"] += 1

    def __enter__(self):
        lg = self._logger
        self._saved = (lg.level, lg.propagate)
        lg.setLevel(logging.DEBUG)
        lg.propagate = False
        lg.addHandler(self)
        return self

    def __exit__(self, *exc):
        lg = self._logger
        lg.removeHandler(self)
        lg.setLevel(self._saved[0])
        lg.propagate = self._saved[1]
        return False
