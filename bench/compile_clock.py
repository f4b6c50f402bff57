"""Counts what JAX spends building executables, through jax.monitoring.

JAX reports one backend-compile event per executable it builds, whether
XLA compiles it or it is loaded from the persistent compilation cache;
a load also reports a cache hit.  So ``programs - cache_hits`` is the
number of executables XLA compiled, and ``trace_s`` is the time spent
tracing and lowering Python to HLO, which a cache hit does not save.
"""
from __future__ import annotations

import jax

_BACKEND = "/jax/core/compile/backend_compile_duration"
_TRACE = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration")
_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Running totals since construction; ``snapshot()`` reads them."""

    def __init__(self):
        self.build_s = 0.0      # backend compile or cache load
        self.trace_s = 0.0      # tracing and lowering
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == _BACKEND:
            self.build_s += duration
            self.programs += 1
        elif event in _TRACE:
            self.trace_s += duration

    def _event(self, event, **_):
        if event == _HIT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"build_s": self.build_s, "trace_s": self.trace_s,
                "programs": self.programs, "cache_hits": self.cache_hits,
                "compiled": self.programs - self.cache_hits}

    @staticmethod
    def since(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in after}
