"""Spans and counters the benchmark records round its own calls into the
program: host clock, kept in memory, handed over when the run ends. Each
span is also a ``jax.profiler.TraceAnnotation`` of the same name, so a
traced run can put the device's operations and gaps under it."""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List


class Recorder:
    def __init__(self):
        self._lock = threading.Lock()
        self.spans: List[Dict[str, Any]] = []
        self.compiles: List[Dict[str, Any]] = []
        self._listening = False
        self.cache_hits = 0
        self.cache_misses = 0

    @contextmanager
    def span(self, name: str, **attrs):
        import jax
        t0 = time.time()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                rec = {"name": name, "t0": t0, "t1": time.time()}
                rec.update(attrs)
                with self._lock:
                    self.spans.append(rec)

    def listen_for_compiles(self):
        """Every backend compile request in this process from here on,
        cache hit or not: a program met for the first time."""
        import jax
        if self._listening:
            return
        self._listening = True

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                with self._lock:
                    self.compiles.append({"t": time.time(),
                                          "seconds": float(duration)})
        jax.monitoring.register_event_duration_secs_listener(on_duration)

        def on_event(event, **_):
            # jax says whether the persistent cache had the program
            if event.endswith("/cache_hits"):
                self.cache_hits += 1
            elif event.endswith("/cache_misses"):
                self.cache_misses += 1
        jax.monitoring.register_event_listener(on_event)

    def between(self, t0: float, t1: float) -> Dict[str, Any]:
        with self._lock:
            return {
                "spans": [s for s in self.spans
                          if s["t1"] >= t0 and s["t0"] <= t1],
                "compiles": [c for c in self.compiles
                             if t0 <= c["t"] <= t1],
                "compiles_total": len(self.compiles),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "compile_seconds_total": sum(
                    c["seconds"] for c in self.compiles)}
