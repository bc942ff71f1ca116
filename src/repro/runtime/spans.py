"""Host spans and counters of the sweeps, on the profiler's clock.

A span names one host step of a grid call (`repro.sweep.prepare`,
`repro.mc_sweep.wait`, ...).  It always opens a
`jax.profiler.TraceAnnotation`, so a profile of the process shows the
step on its host plane, on the device trace's clock.  While a profiler
session collects (`TraceAnnotation.is_enabled()`), the span is also
recorded in memory: name, start and end in `time.perf_counter_ns()`, the
span open around it on the same thread, the grid call it belongs to and
its counts.  Nothing else turns recording on, and with no profiler
session a span costs one annotation and one `is_enabled()` check.

    with spans.span("repro.sweep.prepare", events=n):
        ...
        spans.count("rows", r)        # adds to the innermost open span
    spans.records()                   # what readers and operators read

`spanned(name, **counts_of)` puts a whole function in a span.

Every span of one grid call shares the call id of the root span (the one
opened with no recorded span around it).  While recording, the first
recorded span registers one `jax.monitoring` listener that adds
`compiles` and `compile_s` to the innermost open span, so a compile
shows on the step that caused it.  No span may open inside a jitted
function, and recorded times never reach a result.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import threading
import time
import types
from typing import Callable, Mapping, Optional, Tuple

import jax

CAPACITY = 65_536        # records kept; the oldest go first
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_annotation = jax.profiler.TraceAnnotation
_records: collections.deque = collections.deque(maxlen=CAPACITY)
_ids = itertools.count()
_open = threading.local()          # .stack: this thread's recorded spans
_listener_lock = threading.Lock()
_listening = False


@dataclasses.dataclass(frozen=True)
class Record:
    """One closed span.  `parent` is the id of the recorded span that was
    open around it on the same thread (None for a root), `call` the id of
    its root, shared by every span of one grid call."""
    id: int
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]
    call: int
    counts: Mapping[str, float]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class _Open:
    __slots__ = ("id", "name", "start_ns", "parent", "call", "counts")


def _stack() -> list:
    s = getattr(_open, "stack", None)
    if s is None:
        s = _open.stack = []
    return s


def _on_duration(event: str, duration: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    s = getattr(_open, "stack", None)
    if s:
        c = s[-1].counts
        c["compiles"] = c.get("compiles", 0) + 1
        c["compile_s"] = c.get("compile_s", 0.0) + duration


def _listen() -> None:
    global _listening
    with _listener_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True


class _Span:
    __slots__ = ("_name", "_counts", "_annotation", "_rec")

    def __init__(self, name: str, counts: dict):
        self._name, self._counts = name, counts

    def __enter__(self):
        self._annotation = _annotation(self._name)
        self._annotation.__enter__()
        self._rec = None
        if _annotation.is_enabled():
            if not _listening:
                _listen()
            s = _stack()
            r = _Open()
            r.id = next(_ids)
            r.name = self._name
            r.parent = s[-1].id if s else None
            r.call = s[-1].call if s else r.id
            r.counts = dict(self._counts)
            s.append(r)
            self._rec = r
            r.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        r = self._rec
        if r is not None:
            end = time.perf_counter_ns()
            _stack().pop()
            _records.append(Record(r.id, r.name, r.start_ns, end, r.parent,
                                   r.call, types.MappingProxyType(r.counts)))
        self._annotation.__exit__(*exc)
        return False


def span(name: str, **counts: float) -> _Span:
    """A context manager that annotates the profile with `name` and, while
    a profiler session collects, records the span with `counts`."""
    return _Span(name, counts)


def spanned(name: str, **counts_of: Callable):
    """Decorate a function so that each call runs inside `span(name)`, with
    the count `c` set to `counts_of[c](result)`."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                out = fn(*args, **kwargs)
                for c, of in counts_of.items():
                    count(c, of(out))
            return out
        return call
    return wrap


def count(name: str, n: float) -> None:
    """Add `n` to the count `name` of the innermost recorded open span; a
    no-op when nothing records."""
    s = getattr(_open, "stack", None)
    if s:
        c = s[-1].counts
        c[name] = c.get(name, 0) + n


def records() -> Tuple[Record, ...]:
    """The recorded spans, oldest first by closing time."""
    return tuple(_records)


def clear() -> None:
    """Forget every recorded span (tests)."""
    _records.clear()
