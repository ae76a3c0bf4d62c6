"""From a profiler trace to device busy time, idle gaps and top device ops.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists: each device's operations (line "XLA Ops" of the planes
``/device:TPU:<k>``) and the host spans that the benchmark's wrappers open
(``jax.profiler.TraceAnnotation``), all in nanoseconds on the trace's one
clock.  ``reduce`` works on those lists alone, so it is tested on a small
recorded trace kept as a JSON fixture.
"""

from __future__ import annotations

import heapq
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "window"  # the span around the traced frames
TOP = 10


def load(path: str, span_names) -> dict:
    """``{"devices": [[[start, dur, op], ...] per device], "spans": [[start,
    dur, name, thread], ...]}`` from one ``.xplane.pb``; devices in index
    order, spans only of ``span_names``."""
    from jax.profiler import ProfileData

    wanted = set(span_names) | {WINDOW}
    devices, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = [
                [ev.start_ns, ev.duration_ns, ev.name]
                for line in plane.lines if line.name == OPS_LINE
                for ev in line.events
            ]
        elif plane.name == "/host:CPU":
            for thread, line in enumerate(plane.lines):
                spans += [[ev.start_ns, ev.duration_ns, ev.name, thread]
                          for ev in line.events if ev.name in wanted]
    return {"devices": [devices[k] for k in sorted(devices)], "spans": spans}


def op_name(name: str) -> str:
    """``%mandelbrot_tile.1 = s32[8,128] custom-call(...)`` -> ``mandelbrot_tile``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _union(intervals, t0, t1):
    """Merged [start, end) intervals clipped to [t0, t1]."""
    out = []
    for a, b in sorted((max(a, t0), min(b, t1)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _labels(spans, points):
    """For each of the ascending times ``points``, what the host was doing:
    the names of the spans open then, joined by ``+``."""
    spans = sorted((a, a + d, name) for a, d, name, _ in spans if name != WINDOW)
    open_, k, out = [], 0, []
    for t in points:
        while k < len(spans) and spans[k][0] <= t:
            heapq.heappush(open_, (spans[k][1], spans[k][2]))
            k += 1
        while open_ and open_[0][0] <= t:
            heapq.heappop(open_)
        out.append("+".join(sorted({name for _, name in open_})) or "no_span")
    return out


def reduce(rec: dict) -> dict:
    """Window, per-device busy seconds, top device ops and idle gaps.

    The window is the first ``window`` span.  Busy time is the union of a
    device's operation intervals inside it; an op's time is its part inside
    it, summed over the devices.  Idle gaps are the holes in the
    first device's busy union, each named by the benchmark spans open on any
    host thread at its midpoint, summed by name.
    """
    win = next(s for s in rec["spans"] if s[2] == WINDOW)
    t0, t1 = win[0], win[0] + win[1]
    busy, op_time, unions = [], defaultdict(float), []
    for ops in rec["devices"]:
        unions.append(_union(((a, a + d) for a, d, _ in ops), t0, t1))
        busy.append(sum(b - a for a, b in unions[-1]) / 1e9)
        for a, d, name in ops:
            inside = min(a + d, t1) - max(a, t0)
            if inside > 0:
                op_time[op_name(name)] += inside / 1e9
    holes, edge = [], t0
    for a, b in (unions[0] if unions else []) + [[t1, t1]]:
        if a > edge:
            holes.append((edge, a))
        edge = b
    gaps = defaultdict(float)
    for (a, b), label in zip(holes, _labels(rec["spans"], [(a + b) / 2 for a, b in holes])):
        gaps[label] += (b - a) / 1e9
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy,
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": [[k, v] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]],
    }
