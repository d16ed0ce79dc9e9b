"""Reduce a profiler trace to the benchmark's device readings.

The run wraps its measured window in a host span ``bench.window`` and
each piece of its own host work in ``bench.<what>`` spans
(``jax.profiler.TraceAnnotation``).  From the ``.xplane.pb`` the
profiler writes, this module takes:

* ``window_s``: the length of ``bench.window``, cut where the device's
  trace buffers ran out (a ``Trace Buffers Dropped`` event): the TPU
  keeps about 6.3 million operation events, and the interior point's
  loops fill that within seconds, so the traced window is the part
  the trace holds whole;
* ``busy_s``: the union of the intervals in which an operation ran on
  a device (the ``XLA Ops`` line of each device plane) inside the
  window, averaged over the devices that ran any;
* ``device_ops``: device seconds by operation (its HLO name, the part
  of the event's name before `` = ``), most first;
* ``idle_gaps``: the longest stretches of the window with no device
  operation, each named for the innermost host span around its middle:
  a ``bench.*`` span of the harness (named without its prefix) or a
  ``dlt.*`` span of the program (``repro.tracing``, named in full), and
  ``other`` where there is none.

Host and device events share the profiler's clock.
"""

from __future__ import annotations

import glob
import os
from typing import Optional

SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "dlt."
WINDOW = "window"
OPS_LINE = "XLA Ops"
DROPPED = "Trace Buffers Dropped"
TOP = 10


def xplane_file(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def union(intervals) -> list:
    """Merged, sorted ``[start, end]`` pairs covering ``intervals``."""
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def reduce(planes, device_prefix: str = "/device:") -> Optional[dict]:
    """Readings from ``planes`` (a ``ProfileData``'s, or alike).

    ``device_prefix`` picks the device planes: on a TPU host
    ``/device:TPU:<k>``.  Returns ``None`` where the trace holds no
    window span or no device operation inside it.
    """
    spans, device, cuts = [], [], []
    for plane in planes:
        if plane.name.startswith(device_prefix):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.append([(ev.start_ns, ev.end_ns, ev.name)
                                   for ev in line.events])
                else:
                    cuts += [ev.start_ns for ev in line.events
                             if ev.name == DROPPED]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      ev.start_ns, ev.end_ns))
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    windows = [(a, b) for name, a, b in spans if name == WINDOW]
    if not windows:
        return None
    w0, w1 = windows[0]
    w1 = min([w1] + cuts)
    if w1 <= w0:
        return None
    busy, by_name, merged_all = [], {}, []
    for events in device:
        clipped = [(max(a, w0), min(b, w1), name) for a, b, name in events
                   if b > w0 and a < w1]
        if not clipped:
            continue
        merged = union((a, b) for a, b, _ in clipped)
        busy.append(sum(b - a for a, b in merged))
        merged_all.append(merged)
        for a, b, name in clipped:
            name = name.split(" = ", 1)[0]   # "%while.12 = (...) while(...)"
            by_name[name] = by_name.get(name, 0.0) + (b - a) * 1e-9
    if not busy:
        return None
    # gaps of the device that ran longest (one chip: the chip)
    merged = max(merged_all, key=lambda m: sum(b - a for a, b in m))
    edges = [w0] + [x for ab in merged for x in ab] + [w1]
    gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]
    host = [(name, a, b) for name, a, b in spans if name != WINDOW]

    def what(a, b):
        mid = 0.5 * (a + b)
        around = [(b2 - a2, name) for name, a2, b2 in host if a2 <= mid <= b2]
        return min(around)[1] if around else "other"

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "cut": bool(cuts) and w1 < windows[0][1],
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "devices": len(busy),
        "device_ops": sorted(([n, s] for n, s in by_name.items()),
                             key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": [[what(a, b), (b - a) * 1e-9] for a, b in gaps[:TOP]],
    }


def reduce_dir(trace_dir: str, device_prefix: str = "/device:") -> Optional[dict]:
    """:func:`reduce` of the newest trace under ``trace_dir``."""
    path = xplane_file(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path).planes, device_prefix)
