"""Host-clock timers, JAX's compile events, and the harness's host
spans.

Spans are ``jax.profiler.TraceAnnotation``s: they land in the
profiler's trace on the same clock as the device's operations, so an
idle gap on the device can be attributed to what the host was doing.
Outside a trace they cost a few hundred nanoseconds each.
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Dict

import jax

# JAX's monitoring events for tracing, lowering and XLA compilation
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileWatch:
    """Seconds JAX spent tracing and compiling, and how many programs
    it compiled or fetched from the persistent cache.  JAX's listeners
    cannot be removed: make one per run."""

    def __init__(self):
        self.seconds = 0.0
        self.counts: Dict[str, int] = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)

    def _on_dur(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
            self.counts[event] += 1

    def programs(self) -> int:
        """Programs compiled by XLA or read from the persistent cache
        (JAX times both under one event), so far."""
        return self.counts[BACKEND_COMPILE]


class Timers:
    """Named host-clock durations, summed over repeats."""

    def __init__(self):
        self.s: Dict[str, float] = collections.defaultdict(float)

    @contextlib.contextmanager
    def time(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.s[name] += time.perf_counter() - t0


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


def spanned(name: str, fn):
    """``fn`` run inside the host span ``name``."""
    def call(*a, **kw):
        with span(name):
            return fn(*a, **kw)
    return call
