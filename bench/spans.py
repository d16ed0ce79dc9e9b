"""Program spans (``repro.tracing``) reduced to time per call.

A traced run records the program's spans over its window and hands
them to the readers as ``run["spans"]``: ``repro.tracing.Span`` tuples
(``id``, ``name``, ``start_ns``, ``end_ns``, ``parent``, ...).  Each
engine call is one ``dlt.solve_batch`` root with the layers' spans
under it.
"""

from __future__ import annotations

from typing import Iterable, Optional

CALL = "dlt.solve_batch"


def ms_per_call(spans, names: Iterable[str]) -> Optional[float]:
    """Milliseconds in spans named ``names`` per ``dlt.solve_batch``.

    ``None`` where no span was recorded, no call was, or none of
    ``names`` is there.
    """
    names = set(names)
    calls = sum(1 for s in spans or () if s.name == CALL)
    ns = [s.end_ns - s.start_ns for s in spans or () if s.name in names]
    if not calls or not ns:
        return None
    return sum(ns) * 1e-6 / calls


def per_name(spans) -> dict:
    """:func:`ms_per_call` of each span name recorded."""
    return {name: ms_per_call(spans, [name])
            for name in sorted({s.name for s in spans or ()})}
