"""In-program spans, with the compiles made inside each.

``span(name, **attrs)`` marks one piece of host work at a layer
boundary (``engine.*`` in the serving tick, ``iolm.*`` in instance
optimization).  Three states:

* **off** (the default, no profiler running): ``span`` returns one
  shared no-op context after a flag check and a check of the profiler.
  Nothing is allocated or recorded.
* **profiler running, recorder off**: the span is a bare
  ``jax.profiler.TraceAnnotation(name)``, so a trace taken by any
  caller shows the program's own spans on the device's clock.
* **recording** (after ``enable()``): each span also keeps its
  ``perf_counter_ns`` start and end, its parent (the innermost span
  open on the same thread), its ``attrs``, and the JAX compiles made
  while it was the innermost open span: ``compiles`` counts XLA
  compilations, persistent-cache fetches included, and ``compile_s``
  sums their tracing, lowering and compile seconds.

``with span(...) as rec`` binds ``rec`` to the record only while
recording (``None`` otherwise), so a caller computes costly attributes
under ``if rec:``.  Records stay in memory until ``reset()``;
``snapshot()`` totals them per span name and ``spans()`` returns them.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional

import jax

# JAX's monitoring events for tracing, lowering and XLA compilation
# (jax/_src/dispatch.py); the last also times persistent-cache fetches
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_BACKEND_COMPILE = _COMPILE_EVENTS[-1]

_profiling = jax.profiler.TraceAnnotation.is_enabled
_on = False
_listening = False
_local = threading.local()
_records: List["Span"] = []       # list.append is atomic under the GIL


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


class _Annotation(jax.profiler.TraceAnnotation):
    """A profiler annotation alone (recorder off)."""

    def __enter__(self):
        super().__enter__()
        return None


class Span:
    """One recorded span; ``parent`` is the enclosing span's record."""
    __slots__ = ("name", "attrs", "parent", "start_ns", "end_ns",
                 "child_ns", "compiles", "compile_s", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.parent: Optional[Span] = None
        self.start_ns = self.end_ns = self.child_ns = 0
        self.compiles = 0
        self.compile_s = 0.0

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        stack = _stack()
        self.parent = stack[-1] if stack else None
        stack.append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = time.perf_counter_ns()
        _stack().pop()
        if self.parent is not None:
            self.parent.child_ns += self.end_ns - self.start_ns
        _records.append(self)
        return self._ann.__exit__(*exc)


def _stack() -> List[Span]:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


def span(name: str, **attrs):
    if not _on:
        return _Annotation(name) if _profiling() else _NOOP
    return Span(name, attrs)


def _on_compile(event: str, duration: float, **_) -> None:
    if not _on or event not in _COMPILE_EVENTS:
        return
    stack = _stack()
    if stack:
        stack[-1].compile_s += duration
        stack[-1].compiles += event == _BACKEND_COMPILE


def enable() -> None:
    """Record every span from now on.  The first call registers the one
    compile listener (JAX's listeners cannot be removed)."""
    global _on, _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _listening = True
    _on = True


def disable() -> None:
    global _on
    _on = False


def reset() -> None:
    """Forget every record."""
    _records.clear()


def spans() -> List[Span]:
    """The records, in the order the spans ended."""
    return list(_records)


def snapshot() -> Dict[str, Dict[str, float]]:
    """Totals per span name: ``count``, ``total_s``, ``self_s`` (each
    span's duration less its children's), ``compiles``, ``compile_s``
    (the compiles made while the span was the innermost one open)."""
    out: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: {"count": 0, "total_s": 0.0, "self_s": 0.0, "compiles": 0,
                 "compile_s": 0.0})
    for r in spans():
        t = out[r.name]
        t["count"] += 1
        t["total_s"] += r.seconds
        t["self_s"] += (r.end_ns - r.start_ns - r.child_ns) / 1e9
        t["compiles"] += r.compiles
        t["compile_s"] += r.compile_s
    return dict(out)
