"""Span tracer emitting Chrome trace-event JSON (Perfetto-loadable).

The streaming hot loop is a four-thread pipeline — prefetcher pre-stage
(disk z read), H2D stager, the dispatching driver, and the D2H
write-back daemon — and its whole point is *overlap*. A serialized
profile (``repro.perf.PhaseTimers``) can say which phase costs most,
but only a per-thread timeline shows whether the overlap actually
happens and where the bubbles are. ``SpanTracer`` records wall-time
spans from any thread and serializes them in the Chrome trace-event
format, one track per thread, so ``chrome://tracing`` / Perfetto
(https://ui.perfetto.dev) render the pipeline directly.

Event kinds used (see the trace-event format spec):

  * ``X`` complete events — a named span with ``ts``/``dur`` in
    microseconds, on the emitting thread's track (``span``).
  * ``b``/``e`` async events — request-scoped spans that start and end
    on different threads (a serve request's queue wait spans submit on
    the caller thread to slot-bind on a worker), grouped by
    ``(cat, id)`` (``async_begin``/``async_end``).
  * ``M`` metadata (thread names, emitted automatically on a thread's
    first span).

While recording, every ``span`` also opens a ``jax.profiler``
``TraceAnnotation`` named ``repro.<span name>`` on its thread, so a
``jax.profiler`` trace shows the program's spans in its host plane, on
the device trace's clock (async request spans stay JSON-only). A
``gc.callbacks`` hook records each collection as a ``python.gc`` span
(``generation`` argument) on the collecting thread, so a host stall
reads as a collection or not.

Disabled (the default), every emit point is one attribute check
returning a shared no-op context manager (no jax call, no lock) — the
hot loop's per-block cost is a few hundred nanoseconds, far below the
<3% budget the acceptance bar sets, and the recorded computation is
untouched either way (tracing never syncs the device; spans around
async dispatches measure dispatch, while device-side work shows up in
the write-back thread's materialize span, which is where the pipeline
waits on it).

Events buffer in memory (bounded by ``max_events``; overflow drops and
counts) and land on ``save()``.
"""

from __future__ import annotations

import gc
import json
import threading
import time
from typing import Optional

ANNOTATION_PREFIX = "repro."


class _NullSpan:
    """Shared no-op context manager: the disabled-tracer fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """A same-thread span: the JSON event and the profiler annotation
    while the tracer records, and the wall milliseconds added to
    ``add_ms`` (a counter) when one is given."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_add_ms", "_note",
                 "_t0")

    def __init__(self, tracer, name, cat, args, add_ms=None):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._add_ms = add_ms

    def __enter__(self):
        tr = self._tracer
        self._note = None
        if tr.enabled:
            self._note = tr._annotation(ANNOTATION_PREFIX + self._name)
            self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._note is not None:
            self._note.__exit__(None, None, None)
            self._tracer._emit_complete(
                self._name, self._cat, self._t0, t1 - self._t0, self._args
            )
        if self._add_ms is not None:
            self._add_ms.inc((t1 - self._t0) * 1e3)
        return False


class SpanTracer:
    """Collects trace events; disabled until ``start()``.

    All timestamps come from ``time.perf_counter`` relative to the
    tracer's epoch (set at ``start``), so spans recorded on different
    threads share one monotonic timeline.
    """

    def __init__(self, max_events: int = 2_000_000):
        self.enabled = False
        self.max_events = max_events
        self.dropped = 0
        # reentrant: the gc hook may fire inside an allocation made while
        # this thread already holds the lock, and then emits a span
        self._lock = threading.RLock()
        self._events: list[dict] = []
        self._epoch = time.perf_counter()
        # thread ident -> (small tid, thread name). The name is part of
        # the entry because the OS reuses idents: a pipeline thread that
        # dies between iterations can hand its ident to a differently
        # named successor, which must get its OWN track, not the old one.
        self._tids: dict[int, tuple[int, str]] = {}
        self._next_tid = 0
        self._path: Optional[str] = None
        self._annotation = None
        self._gc_open: dict[int, _Span] = {}
        self._gc_hook = self._on_gc

    # -- lifecycle ---------------------------------------------------------
    def start(self, path: Optional[str] = None):
        """Begin recording; ``path`` (if given) is the default
        ``save()`` destination."""
        from jax.profiler import TraceAnnotation

        with self._lock:
            self._path = path or self._path
            self._epoch = time.perf_counter()
            self._events.clear()
            self._tids.clear()
            self._next_tid = 0
            self.dropped = 0
            self._annotation = TraceAnnotation
            self.enabled = True
        if self._gc_hook not in gc.callbacks:
            gc.callbacks.append(self._gc_hook)

    def stop(self):
        self.enabled = False
        if self._gc_hook in gc.callbacks:
            gc.callbacks.remove(self._gc_hook)
        self._gc_open.clear()

    def _on_gc(self, phase, info):
        """``gc.callbacks`` hook: a ``python.gc`` span from a
        collection's start to its stop, on the collecting thread."""
        ident = threading.get_ident()
        if phase == "start":
            if self.enabled:
                span = _Span(self, "python.gc", "gc",
                             {"generation": info["generation"]})
                span.__enter__()
                self._gc_open[ident] = span
        else:
            span = self._gc_open.pop(ident, None)
            if span is not None:
                span.__exit__(None, None, None)

    # -- emit --------------------------------------------------------------
    def _tid_locked(self) -> int:
        th = threading.current_thread()
        ent = self._tids.get(th.ident)
        if ent is None or ent[1] != th.name:
            tid = self._next_tid
            self._next_tid += 1
            self._tids[th.ident] = (tid, th.name)
            self._events.append({
                "ph": "M", "name": "thread_name", "pid": 1, "tid": tid,
                "args": {"name": th.name},
            })
            return tid
        return ent[0]

    def _append(self, ev: dict):
        """Append under the lock unless the buffer is full, filling in
        the event's ``tid``."""
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            ev["tid"] = self._tid_locked()
            self._events.append(ev)

    def _emit_complete(self, name, cat, t0, dur, args):
        ev = {"ph": "X", "name": name, "cat": cat or "span", "pid": 1,
              "tid": None, "ts": round((t0 - self._epoch) * 1e6, 3),
              "dur": round(dur * 1e6, 3)}
        if args:
            ev["args"] = args
        self._append(ev)

    def span(self, name: str, cat: str = "", *, add_ms=None, **args):
        """Context manager timing a same-thread span; the no-op
        singleton when disabled. ``add_ms``, a counter, also receives
        the span's wall milliseconds, recorded or not."""
        if not self.enabled and add_ms is None:
            return _NULL_SPAN
        return _Span(self, name, cat, args, add_ms)

    def _emit_async(self, ph, name, cat, aid, args):
        if not self.enabled:
            return
        ev = {"ph": ph, "name": name, "cat": cat, "id": str(aid), "pid": 1,
              "tid": None,
              "ts": round((time.perf_counter() - self._epoch) * 1e6, 3)}
        if args:
            ev["args"] = args
        self._append(ev)

    def async_begin(self, name: str, aid, cat: str = "async", **args):
        """Start a span that may end on another thread (e.g. a serve
        request's lifecycle). Pair with ``async_end`` via (cat, id)."""
        self._emit_async("b", name, cat, aid, args)

    def async_end(self, name: str, aid, cat: str = "async", **args):
        self._emit_async("e", name, cat, aid, args)

    # -- output ------------------------------------------------------------
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def save(self, path: Optional[str] = None) -> Optional[str]:
        """Write the Chrome trace JSON (object form, ``traceEvents``
        key); returns the path, or None when there is nowhere to save.
        Callable repeatedly — each save serializes the current buffer."""
        path = path or self._path
        if path is None:
            return None
        with self._lock:
            events = list(self._events)
            dropped = self.dropped
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"producer": "repro.obs", "dropped_events": dropped},
        }
        with open(path, "w") as f:
            json.dump(doc, f)
        return path
