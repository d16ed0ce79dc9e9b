"""Host spans of the program, on the profiler's clock.

A span names one stretch of host work::

    from repro import tracing

    with tracing.span("dlt.assemble"):
        ...

With no recorder active, :func:`span` returns one shared null context:
the cost is a global read, nothing is allocated and nothing reaches the
profiler.  Inside :func:`recording`, each span is kept in memory as a
:class:`Span` on ``time.perf_counter_ns`` and its body is wrapped in
``jax.profiler.TraceAnnotation(name)``, so that the same span shows in a
profiler trace beside the device's operations.  Spans nest per thread:
``parent`` is the span open around it on the same thread, and ``call``
is the id of the outermost one, shared by every span under that root.

Whoever opens the recorder owns its spans; there is no exporter.
:meth:`Recorder.summary` gives, per name, the number of spans, their
total time and their self time (what no child of theirs covers).
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Dict, Iterator, List, NamedTuple, Optional

import jax

__all__ = ["Recorder", "Span", "active", "recording", "span"]


class Span(NamedTuple):
    """One closed span.  ``parent`` is ``None`` for a root."""

    id: int
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]
    call: int
    thread: int
    attrs: dict


class _NullSpan:
    """What :func:`span` returns while nothing records."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **attrs) -> None:
        """Attributes known only inside the span (ignored here)."""


_NULL = _NullSpan()
_ACTIVE: Optional["Recorder"] = None


def span(name: str, **attrs):
    """A context manager timing its body as span ``name``.

    ``attrs`` (and later :meth:`set` calls on what ``with`` yields) are
    kept with the span; they never reach the profiler.
    """
    rec = _ACTIVE
    if rec is None:
        return _NULL
    return _OpenSpan(rec, name, attrs)


def active() -> bool:
    """Whether a recorder is active (spans are being kept)."""
    return _ACTIVE is not None


@contextlib.contextmanager
def recording() -> Iterator["Recorder"]:
    """Record every span of the process until the block ends."""
    global _ACTIVE
    rec, prev = Recorder(), _ACTIVE
    _ACTIVE = rec
    try:
        yield rec
    finally:
        _ACTIVE = prev


class _OpenSpan:
    __slots__ = ("rec", "name", "attrs", "id", "parent", "call", "start",
                 "annotation")

    def __init__(self, rec: "Recorder", name: str, attrs: dict):
        self.rec, self.name, self.attrs = rec, name, attrs

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "_OpenSpan":
        stack = self.rec._stack()
        up = stack[-1] if stack else None
        self.id = next(self.rec._ids)
        self.parent = None if up is None else up.id
        self.call = self.id if up is None else up.call
        stack.append(self)
        self.annotation = jax.profiler.TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        self.annotation.__exit__(*exc)
        self.rec._stack().pop()
        self.rec._add(Span(self.id, self.name, self.start, end, self.parent,
                           self.call, threading.get_ident(), self.attrs))


class Recorder:
    """The spans of one recording, from any number of threads."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count()
        self._spans: List[Span] = []

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _add(self, s: Span) -> None:
        with self._lock:
            self._spans.append(s)

    @property
    def spans(self) -> List[Span]:
        """The closed spans, in the order they closed."""
        with self._lock:
            return list(self._spans)

    def summary(self) -> Dict[str, dict]:
        """Per span name: ``count``, ``total_s`` and ``self_s``.

        A span's self time is its length less the union of its
        children's intervals (clipped to it).
        """
        spans = self.spans
        children: Dict[int, list] = {}
        for s in spans:
            if s.parent is not None:
                children.setdefault(s.parent, []).append(s)
        out: Dict[str, dict] = {}
        for s in spans:
            kids = [(max(c.start_ns, s.start_ns), min(c.end_ns, s.end_ns))
                    for c in children.get(s.id, ())]
            row = out.setdefault(s.name,
                                 {"count": 0, "total_s": 0.0, "self_s": 0.0})
            row["count"] += 1
            row["total_s"] += (s.end_ns - s.start_ns) * 1e-9
            row["self_s"] += (s.end_ns - s.start_ns
                              - _covered(kids)) * 1e-9
        return out


def _covered(intervals) -> int:
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0, None
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if reach is None or a > reach:
            total += b - a
            reach = b
        elif b > reach:
            total += b - reach
            reach = b
    return total
