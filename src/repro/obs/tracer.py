"""Span tracer on the profiler's clock: context-manager/decorator API,
thread-aware, with exact per-name aggregates.

The serving stack (engine stages, the engine's decode tick and
admission, the orchestrator loop, speculative rounds, the page
allocator) opens *spans* around units of work::

    tracer = Tracer()
    with tracer.span("engine.sample"):
        toks = sample(logits)

    @tracer.trace("detok", cat="detok")
    def detokenize(...): ...

Design points:

* **One clock: the profiler's.**  While a profiler session is active
  (``jax.profiler.start_trace``), every span is also a
  ``jax.profiler.TraceAnnotation`` of the same name, its keyword args
  as metadata.  The spans land in the same ``.xplane.pb`` as the
  device's operations, on one clock, so an idle stretch of the device
  can be named by the host work that held it.  A profiler session is
  the switch: there is no flag and no environment variable.
* **Exact aggregates while enabled.**  With ``Tracer.enabled`` each span
  also adds to per-name aggregates (count / total / self seconds,
  ``time.perf_counter``).  Each thread keeps its own span stack, so a
  span's *self time* (duration minus time in child spans on the same
  thread) is computed online at close.  Self times are the currency of
  :func:`repro.obs.report.stage_breakdown`: summed over one thread's
  spans they tile its traced wall time without double counting.
* **Off is (nearly) free.**  With neither on, ``span()`` returns a
  shared no-op context manager after one attribute check and the
  profiler's ``is_enabled`` check (~0.1 µs); the serving hot loop keeps
  its spans in place permanently (bounded by ``tests/test_obs.py``).
* **Collections are spans too.**  Importing this module installs, once
  per process, a ``gc.callbacks`` hook that opens a ``host.gc`` span
  (generation as metadata) around every Python collection while a
  profiler session is active; with none it costs the ``is_enabled``
  check a collection.
"""
from __future__ import annotations

import functools
import gc
import threading
from time import perf_counter
from typing import Any, Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "Span"]

#: True while a profiler session is recording host spans
profiling: Callable[[], bool] = TraceAnnotation.is_enabled


class _NullSpan:
    """Shared no-op context manager returned while tracing is off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()
#: shared no-op span for call sites with no tracer wired at all
NULL_SPAN = _NULL_SPAN


class Span:
    """One live span of an enabled tracer; use via ``with tracer.span(...)``,
    not directly."""
    __slots__ = ("_tracer", "name", "cat", "args", "t0", "_child_s",
                 "_note")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._child_s = 0.0

    def __enter__(self) -> "Span":
        self._note = (TraceAnnotation(self.name, **self.args).__enter__()
                      if profiling() else None)
        self._tracer._stack().append(self)
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dur = perf_counter() - self.t0
        stack = self._tracer._stack()
        # tolerate misuse (exit out of order) without corrupting siblings
        if stack and stack[-1] is self:
            stack.pop()
        if stack:
            stack[-1]._child_s += dur
        self._tracer._add(self.name, self.cat, dur, dur - self._child_s)
        if self._note is not None:
            self._note.__exit__(*exc)
        return False


_gc_open = threading.local()


def _gc_span(phase: str, info: Dict[str, Any]) -> None:
    """``gc.callbacks`` hook: a ``host.gc`` profiler span around each
    collection.  A collection starts and stops on one thread, so the
    open span is kept per thread."""
    if phase == "start":
        if profiling():
            _gc_open.span = TraceAnnotation(
                "host.gc", generation=info["generation"]).__enter__()
        return
    span = getattr(_gc_open, "span", None)
    if span is not None:
        _gc_open.span = None
        span.__exit__(None, None, None)


gc.callbacks.append(_gc_span)


class Tracer:
    """Spans to the profiler while a session is active; exact per-name
    aggregates while ``enabled``."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        # (name, cat) -> [count, total_s, self_s]
        self._agg: Dict[Any, List[float]] = {}
        self._lock = threading.Lock()
        self._tls = threading.local()

    # ---- recording ----
    def span(self, name: str, cat: str = "host", **args) -> Any:
        """Open a span; returns a context manager.  No-op when neither
        enabled nor profiling."""
        if self.enabled:
            return Span(self, name, cat, args)
        if profiling():
            return TraceAnnotation(name, **args)
        return _NULL_SPAN

    def trace(self, name: Optional[str] = None,
              cat: str = "host") -> Callable:
        """Decorator form: ``@tracer.trace("stage")``."""
        def deco(fn: Callable) -> Callable:
            label = name or fn.__qualname__

            @functools.wraps(fn)
            def wrapper(*a, **kw):
                with self.span(label, cat):
                    return fn(*a, **kw)
            return wrapper
        return deco

    def record(self, name: str, t0: float, t1: float,
               cat: str = "host") -> None:
        """Add an already-closed span from external ``perf_counter``
        stamps (e.g. a request's queue wait measured between its submit
        and admit stamps) to the aggregates.  No stack interaction: the
        span never nests, so its self time equals its duration, and —
        unlike ``span()`` — it does not subtract from any live parent
        span.  Use ``cat`` to pick the attribution bucket (``"queue"``
        spans are reported outside the wall-clock sum: a request waiting
        overlaps other requests decoding)."""
        if self.enabled:
            self._add(name, cat, t1 - t0, t1 - t0)

    def _stack(self) -> List[Span]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _add(self, name: str, cat: str, dur: float, self_s: float) -> None:
        key = (name, cat)
        with self._lock:
            agg = self._agg.get(key)
            if agg is None:
                self._agg[key] = [1, dur, self_s]
            else:
                agg[0] += 1
                agg[1] += dur
                agg[2] += self_s

    # ---- control ----
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        """Drop the aggregates (enabled flag unchanged)."""
        with self._lock:
            self._agg.clear()

    # ---- inspection ----
    def self_times(self) -> Dict[str, Dict[str, Any]]:
        """Exact per-span-name aggregates while enabled:
        ``{name: {cat, count, total_s, self_s}}``.  ``self_s`` excludes
        time spent inside child spans, so summing it across names never
        double-counts nested work."""
        with self._lock:
            items = list(self._agg.items())
        out: Dict[str, Dict[str, Any]] = {}
        for (name, cat), (count, total, self_s) in items:
            rec = out.get(name)
            if rec is None:
                out[name] = {"cat": cat, "count": int(count),
                             "total_s": total, "self_s": self_s}
            else:                      # same name under two cats: merge
                rec["count"] += int(count)
                rec["total_s"] += total
                rec["self_s"] += self_s
        return out
