"""Span/trace API — nested wall-clock regions with device-sync semantics.

    with obs.trace.span("prefill", requests=4) as sp:
        out = engine_prefill(...)
        sp.attach(out)          # block_until_ready(out) before t1 is taken

Spans nest (a thread-local stack records parent names and depth), survive
exceptions (the finally path closes the span and marks ``error``), and
emit two things on close:

  * a ``span`` event to the trace sinks (obs/sinks.py JSONL schema),
  * a ``span_ms`` histogram observation labeled by span name.

Durations come from host ``time.perf_counter``. Each span event also
carries ``t0_ns`` / ``t1_ns`` from ``time.time_ns()``, the wall clock the
``jax.profiler`` host tracer stamps its events with, taken inside the
span's ``TraceAnnotation``: a JSONL span lines up with the same region in
a device profile (an ``.xplane.pb`` holds its host events relative to the
``profile_start_time`` stat of its "Task Environment" plane, on that same
clock). Because JAX dispatch is async,
a span around a jitted call measures *dispatch* unless the result is
attached: ``sp.attach(x)`` registers pytrees to ``jax.block_until_ready``
immediately before the end timestamp, so the span covers device work —
the same discipline obs/timing.py uses for benchmark medians.

When the optional ``jax.profiler`` is importable, each span also opens a
``TraceAnnotation`` so device profiles show the same region names; this
is best-effort and never required (offline/test environments).
"""

from __future__ import annotations

import threading
import time
from typing import Any, List, Optional

from repro.obs import metrics as MET
from repro.obs import sinks as SK

_tls = threading.local()


def _stack() -> List["Span"]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current_span() -> Optional["Span"]:
    st = _stack()
    return st[-1] if st else None


class Span:
    """One open trace region. Use via ``span(...)``; not self-registering."""

    def __init__(self, name: str, attrs: Optional[dict] = None):
        parent = current_span()
        self.name = name
        self.attrs = dict(attrs or {})
        self.parent = parent.name if parent else None
        self.depth = parent.depth + 1 if parent else 0
        self.path = (parent.path + "/" + name) if parent else name
        self.error: Optional[str] = None
        self._sync: List[Any] = []
        self._annotation = None
        self.t0 = self.t1 = None
        self.t0_ns = self.t1_ns = None

    # -- lifecycle (driven by the ``span`` context manager) ------------------
    def _open(self):
        _stack().append(self)
        try:  # best-effort device-profiler annotation
            import jax.profiler as _prof

            self._annotation = _prof.TraceAnnotation(self.name)
            self._annotation.__enter__()
        except Exception:
            self._annotation = None
        self.t0_ns = time.time_ns()
        self.t0 = time.perf_counter()
        return self

    def _close(self):
        if self._sync:
            try:
                import jax

                jax.block_until_ready(self._sync)
            except Exception:
                pass
        self.t1 = time.perf_counter()
        self.t1_ns = time.time_ns()
        if self._annotation is not None:
            try:
                self._annotation.__exit__(None, None, None)
            except Exception:
                pass
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        MET.histogram_observe("span_ms", self.duration_ms,
                              labels={"name": self.name})
        if SK.trace_enabled():
            SK.emit_event(self.as_event())

    # -- user API ------------------------------------------------------------
    def attach(self, *values):
        """Register pytrees to block_until_ready before the span closes."""
        self._sync.extend(values)
        return values[0] if len(values) == 1 else values

    def annotate(self, **attrs):
        self.attrs.update(attrs)

    @property
    def duration_ms(self) -> float:
        if self.t0 is None or self.t1 is None:
            return 0.0
        return (self.t1 - self.t0) * 1e3

    def as_event(self) -> dict:
        ev = {"type": "span", "name": self.name, "path": self.path,
              "parent": self.parent, "depth": self.depth,
              "duration_ms": self.duration_ms,
              "t0_ns": self.t0_ns, "t1_ns": self.t1_ns}
        if self.attrs:
            ev["attrs"] = _plain(self.attrs)
        if self.error is not None:
            ev["error"] = self.error
        return ev


def _plain(obj):
    """JSON-able copy: tuples -> lists, numpy scalars -> python."""
    if isinstance(obj, dict):
        return {str(k): _plain(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_plain(v) for v in obj]
    if hasattr(obj, "item") and getattr(obj, "ndim", 1) == 0:
        return obj.item()
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return str(obj)


class _SpanCM:
    def __init__(self, name: str, attrs: dict):
        self._span = Span(name, attrs)

    def __enter__(self) -> Span:
        return self._span._open()

    def __exit__(self, exc_type, exc, tb):
        if exc is not None:
            self._span.error = f"{exc_type.__name__}: {exc}"
        self._span._close()
        return False  # never swallow


def span(name: str, **attrs) -> _SpanCM:
    """Open a nested wall-clock span (context manager yielding the Span)."""
    return _SpanCM(name, attrs)


def timed(name: str):
    """Decorator form: run fn under ``span(name)`` and attach its result."""

    def deco(fn):
        import functools

        @functools.wraps(fn)
        def wrapped(*args, **kw):
            with span(name) as sp:
                return sp.attach(fn(*args, **kw))

        return wrapped

    return deco
