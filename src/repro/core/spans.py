"""Spans: named intervals with parents, on the wall clock.

A span is a plain tuple

    (id, parent_id, name, t0_ns, t1_ns, attrs)

stamped with ``time.time_ns()`` — the clock the lifecycle hops use, and
the clock of a ``jax.profiler`` capture once its ``profile_start_time``
is added to an event's start.  Tuples (not a class) for the same reason
hops are tuples: they cross the proc fabric's wire and the JSONL event log
unchanged.

Spans are always recorded.  They land in a *sink*, a plain list opened by
:func:`collect` (the service opens one per super-batch; a bare
:class:`~repro.core.runtime.Runtime` opens one per run), and the parent of
a new span is the span open on the current thread, held in a
:class:`contextvars.ContextVar`.  Threads that do not inherit the context
(a ``ThreadPoolExecutor``'s workers) take their parent explicitly through
:func:`attach`.  With no sink open, a span records nothing but its
profiler mirror.

Leaf spans (:func:`span`) also enter ``jax.profiler.TraceAnnotation`` for
their duration, so a profiler capture names host time by stratum's layers;
parents (:func:`scope`) are not mirrored, because a trace reader that names
an interval by the host event overlapping it most would otherwise pick the
enclosing parent every time.

Compiles are counted process-wide from JAX's monitoring events (trace,
lowering, backend compile): :func:`compile_totals` gives the count of
backend compiles and the seconds of all three, nested events counted once,
and each event is also recorded as a ``stratum.compile`` span under the
span open on the compiling thread.
"""

from __future__ import annotations

import contextvars
import itertools
import threading
import time
from contextlib import contextmanager
from typing import Optional

import jax
from jax.profiler import TraceAnnotation

_ids = itertools.count(1)


class _Root:
    """The parent of a collection's top-level spans: a sink, no id."""

    __slots__ = ("sink", "id")

    def __init__(self, sink: list):
        self.sink = sink
        self.id = None


_current: contextvars.ContextVar = contextvars.ContextVar(
    "stratum_span", default=None)


class Span:
    """An open span; appended to its sink as a tuple when it closes.

    ``mirror`` is the profiler annotation's name, or None for a span that
    is not mirrored.  ``attrs`` may be updated while the span is open."""

    __slots__ = ("name", "attrs", "mirror", "sink", "id", "parent", "t0",
                 "_token", "_ann")

    def __init__(self, name: str, attrs: dict, mirror: Optional[str]):
        self.name = name
        self.attrs = attrs
        self.mirror = mirror

    def __enter__(self) -> "Span":
        cur = _current.get()
        self.sink = cur.sink if cur is not None else None
        self.parent = cur.id if cur is not None else None
        self.id = next(_ids)
        self._token = _current.set(self)
        self._ann = None
        if self.mirror is not None:
            self._ann = TraceAnnotation(self.mirror)
            self._ann.__enter__()
        self.t0 = time.time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.time_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _current.reset(self._token)
        if self.sink is not None:
            self.sink.append((self.id, self.parent, self.name, self.t0, t1,
                              self.attrs))
        return False


def span(name: str, **attrs) -> Span:
    """A leaf span, mirrored into a profiler capture under ``name``."""
    return Span(name, attrs, name)


def scope(name: str, **attrs) -> Span:
    """A span that holds others; not mirrored."""
    return Span(name, attrs, None)


def opens_spans(fn):
    """Mark an op implementation that opens leaf spans of its own: the
    runtime then leaves its op span out of the profiler capture."""
    fn.opens_spans = True
    return fn


def current():
    """The span open on this thread (or the open collection's root), or
    None: what :func:`attach` hands to another thread."""
    return _current.get()


@contextmanager
def collect(sink: list):
    """Record the spans opened inside into ``sink``, as a new tree."""
    token = _current.set(_Root(sink))
    try:
        yield
    finally:
        _current.reset(token)


@contextmanager
def attach(parent):
    """Open spans inside under ``parent`` (from :func:`current` on the
    thread that handed the work over)."""
    token = _current.set(parent)
    try:
        yield
    finally:
        _current.reset(token)


def annotate(**attrs) -> None:
    """Add attributes to the span open on this thread."""
    cur = _current.get()
    if cur is not None and cur.id is not None:
        cur.attrs.update(attrs)


def record(name: str, t0_ns: int, t1_ns: int, parent=None,
           **attrs) -> Optional[tuple]:
    """Record an interval known only after the fact (a queue wait, a
    compile) under ``parent``, by default the span open on this thread.
    Returns the span, or None when no sink is open."""
    if parent is None:
        parent = _current.get()
    if parent is None or parent.sink is None:
        return None
    s = (next(_ids), parent.id, name, int(t0_ns), int(t1_ns), attrs)
    parent.sink.append(s)
    return s


# ---------------------------------------------------------------------------
# reading spans
# ---------------------------------------------------------------------------

def _union_ns(intervals) -> int:
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def self_times(spans) -> dict:
    """``{span id: self ns}``: a span's duration less the union of its
    children's intervals, each clipped to the span."""
    children: dict = {}
    for s in spans:
        children.setdefault(s[1], []).append(s)
    out = {}
    for sid, _parent, _name, t0, t1, _attrs in spans:
        covered = _union_ns((max(c[3], t0), min(c[4], t1))
                            for c in children.get(sid, ())
                            if min(c[4], t1) > max(c[3], t0))
        out[sid] = (t1 - t0) - covered
    return out


def self_seconds_by_name(spans) -> dict:
    """``{name: (self seconds, count)}`` over ``spans``."""
    selfs = self_times(spans)
    out: dict = {}
    for s in spans:
        sec, n = out.get(s[2], (0.0, 0))
        out[s[2]] = (sec + selfs[s[0]] * 1e-9, n + 1)
    return out


# ---------------------------------------------------------------------------
# compiles, process-wide
# ---------------------------------------------------------------------------

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
_BACKEND_COMPILE = COMPILE_EVENTS[2]
_RECENT = 32      # outermost compile intervals remembered per thread


class _Compiles:
    """Backend compiles and compile seconds of this process.  A trace of a
    jitted function that calls other jitted functions reports the inner
    traces, first, inside the outer one; each thread remembers its latest
    outermost intervals, so nested seconds count once and an outer
    compile span becomes the parent of the inner ones."""

    def __init__(self):
        self.lock = threading.Lock()
        self.n = 0
        self.s = 0.0
        self.local = threading.local()

    def on_event(self, event: str, start: float, end: float,
                 **kwargs) -> None:
        if event not in COMPILE_EVENTS:
            return
        recent = getattr(self.local, "recent", None)
        if recent is None:
            recent = self.local.recent = []
        # (start, end, span or None, sink) of this thread's outermost
        # intervals so far
        inner = [iv for iv in recent if start <= iv[0] and iv[1] <= end]
        exclusive = (end - start) - sum(iv[1] - iv[0] for iv in inner)
        with self.lock:
            self.s += max(exclusive, 0.0)
            if event == _BACKEND_COMPILE:
                self.n += 1
        cur = _current.get()
        s = record("stratum.compile", int(start * 1e9), int(end * 1e9),
                   parent=cur, fun_name=str(kwargs.get("fun_name", "")),
                   event=event.rsplit("/", 1)[-1])
        if s is not None:
            for iv in inner:
                _reparent(iv[3], iv[2], s[0])
        recent[:] = [iv for iv in recent if iv not in inner]
        recent.append((start, end, s, cur.sink if s is not None else None))
        del recent[:-_RECENT]

    def totals(self) -> dict:
        with self.lock:
            return {"n": self.n, "s": self.s}


def _reparent(sink, child, parent_id) -> None:
    """Put ``child``, already in ``sink``, under ``parent_id``."""
    if child is None:
        return
    for i in range(len(sink) - 1, -1, -1):
        if sink[i] is child:
            sink[i] = (child[0], parent_id) + child[2:]
            return


_compiles = _Compiles()
jax.monitoring.register_event_time_span_listener(_compiles.on_event)


def compile_totals() -> dict:
    """``{"n": backend compiles, "s": compile seconds}`` of this process
    since it imported stratum."""
    return _compiles.totals()
