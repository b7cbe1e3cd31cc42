"""Reduction of a profiler trace to the device's busy time, idle share and
``breakdown``.

The JAX profiler writes one ``.xplane.pb`` per traced run.  In it every
device is a plane named ``/device:<KIND>:<n>`` whose ``XLA Ops`` line holds
one event per operation the device ran, and the host is the
``/host:CPU`` plane, where the benchmark's own ``TraceAnnotation`` spans
(``bench.grid`` around each grid, ``bench.trace_build``, ``bench.submit``,
``bench.drain`` inside it) sit on the Python thread.  All events share one
clock.

The traced window runs from the first ``bench.grid`` span's start to the
last one's end, or to where the device's trace buffers overflowed (the
profiler then marks "Trace Buffers Dropped" on the ``XLA TraceMe`` line
and records nothing more of the device).  A device is busy where at least one of its operations
runs; its idle gaps are the rest of the window, each labelled by the
innermost benchmark span that holds the gap's midpoint.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start, end) in seconds

WINDOW_SPAN = "bench.grid"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
TRACEME_LINE = "XLA TraceMe"
DROPPED = "Trace Buffers Dropped"
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def op_name(text: str) -> str:
    """``"%fusion.12 = f32[16]{0} fusion(...)"`` -> ``"fusion.12 fusion"``:
    the instruction's name and opcode, without its shapes."""
    head, _, rest = text.partition(" = ")
    m = _OPCODE.search(rest)
    return f"{head.lstrip('%')} {m.group(1)}" if m else head.lstrip("%")


def union(intervals: Sequence[Interval], lo: float, hi: float) \
        -> List[Interval]:
    """Sorted, disjoint union of ``intervals`` clipped to [lo, hi]."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``busy`` (sorted, disjoint) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """Name of the shortest host span holding the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    inside = [(e - s, name) for name, s, e in spans if s <= mid <= e]
    return min(inside)[1] if inside else "outside the benchmark's spans"


def reduce_trace(spans: Sequence[Tuple[str, float, float]],
                 ops: Dict[str, Sequence[Tuple[str, float, float]]],
                 dropped_at: Optional[float] = None,
                 top: int = 10) -> Optional[dict]:
    """``spans``: the benchmark's host spans (name, start, end).  ``ops``:
    per device, what ran on it (name, start, end).  ``dropped_at``: where
    the profiler began to drop device events; the traced window ends
    there.  Returns ``busy_s`` (mean over devices), ``window_s``,
    ``device_ops`` (what took most device time, mean over devices) and
    ``idle_gaps`` (the longest gaps of the first device, labelled), or
    None when the trace holds no window or no device operation."""
    win = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    devices = {d: v for d, v in ops.items() if v}
    if not win or not devices:
        return None
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    if dropped_at is not None:
        hi = min(hi, dropped_at)
    if hi <= lo:
        return None
    per_op: Dict[str, float] = collections.defaultdict(float)
    busy_total = 0.0
    first_busy = None
    for d in sorted(devices):
        busy = union([(s, e) for _, s, e in devices[d]], lo, hi)
        busy_total += sum(e - s for s, e in busy)
        if first_busy is None:
            first_busy = busy
        for name, s, e in devices[d]:
            dur = min(e, hi) - max(s, lo)
            if dur > 0:
                per_op[name] += dur / len(devices)
    idle = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": busy_total / len(devices),
        "window_s": hi - lo,
        "device_ops": sorted(([k, v] for k, v in per_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[label(g, spans), g[1] - g[0]] for g in idle],
    }


def read_xplane(path) -> dict:
    """From an ``.xplane.pb``, in seconds: the benchmark's host ``spans``,
    per device its ``ops`` (the ``XLA Ops`` line, or where a trace mode
    records none, the ``XLA Modules`` line), and ``dropped_at``, the
    earliest point from which the profiler dropped device events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    spans, ops, dropped = [], {}, []

    def events(line, name=lambda n: n):
        return [(name(ev.name), ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events]

    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [ev for ev in events(line)
                          if ev[0].startswith("bench.")]
        elif plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OP_LINE in lines:
                ops[plane.name] = events(lines[OP_LINE], op_name)
            if not ops.get(plane.name) and MODULE_LINE in lines:
                ops[plane.name] = events(lines[MODULE_LINE])
            if TRACEME_LINE in lines:
                dropped += [s for n, s, _ in events(lines[TRACEME_LINE])
                            if n == DROPPED]
    return {"spans": spans, "ops": ops,
            "dropped_at": min(dropped) if dropped else None}
