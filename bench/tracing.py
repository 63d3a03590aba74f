"""Reduction of a profiler trace to the benchmark's numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``load`` reads it with ``jax.profiler.ProfileData`` into plain tuples:

* device op intervals: the events of each ``/device:TPU:<i>`` plane's
  ``XLA Ops`` and ``Async XLA Ops`` lines (one event per operation the
  chip ran; the async line holds the copies between memory spaces),
  named by the HLO instruction's name;
* host spans: the events whose name starts with ``bench.`` (the
  ``TraceAnnotation``s the harness puts around its own calls), on any
  host line.

Both are on the profiler's one clock, in nanoseconds.  Busy time is the
union of a device's op intervals; the idle share of a window is one minus
busy over the window's length.  Everything past ``load`` is plain
arithmetic on intervals, so the tests check it on a recorded trace.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]  # (start_ns, end_ns)

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINES = ("XLA Ops", "Async XLA Ops")
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    # device plane name -> [(start_ns, end_ns, op name)]
    ops: Dict[str, List[Tuple[float, float, str]]]
    # [(start_ns, end_ns, span name)] of the harness's host spans
    spans: List[Tuple[float, float, str]]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[str, List[Tuple[float, float, str]]] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        for line in plane.lines:
            if plane.name.startswith(DEVICE_PLANE_PREFIX):
                if line.name in OPS_LINES:
                    ops.setdefault(plane.name, []).extend(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         op_name(ev.name)) for ev in line.events)
                continue
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return Trace(ops=ops, spans=sorted(spans))


def op_name(text: str) -> str:
    """``%fusion.12 = s32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Disjoint, sorted cover of ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two interval sets."""
    ua, ub = union(a), union(b)
    i = j = 0
    total = 0.0
    while i < len(ua) and j < len(ub):
        s = max(ua[i][0], ub[j][0])
        e = min(ua[i][1], ub[j][1])
        if e > s:
            total += e - s
        if ua[i][1] < ub[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` between busy intervals."""
    out, at = [], lo
    for s, e in union(clip(busy, lo, hi)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def device_busy(trace: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Busy nanoseconds of each device inside ``[lo, hi]``."""
    return {plane: length(clip([(s, e) for s, e, _ in evs], lo, hi))
            for plane, evs in trace.ops.items()}


def span_intervals(trace: Trace, name: str) -> List[Interval]:
    return [(s, e) for s, e, n in trace.spans if n == name]


def busy_inside(trace: Trace, spans: Sequence[Interval]) -> float:
    """Device-busy nanoseconds inside the given host spans, averaged over
    the devices."""
    if not trace.ops:
        return 0.0
    return sum(overlap([(s, e) for s, e, _ in evs], spans)
               for evs in trace.ops.values()) / len(trace.ops)


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``k`` operation names with the most device seconds in the
    window, summed over devices (names are the compiler's HLO names)."""
    tot: Dict[str, float] = collections.Counter()
    for evs in trace.ops.values():
        for s, e, name in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                tot[name] += d
    return [(n, v / 1e9) for n, v in
            sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:k]]


def idle_by_host_span(trace: Trace, lo: float, hi: float, k: int = 10
                      ) -> List[Tuple[str, float]]:
    """Idle device seconds in the window, by the harness span the host
    was in (``outside`` where it was in none), summed over devices, the
    ``k`` largest.  The spans other than ``bench.window`` do not nest."""
    inner = union_named([sp for sp in trace.spans
                         if sp[2] != "bench.window"])
    tot: Dict[str, float] = collections.Counter()
    for evs in trace.ops.values():
        idle = gaps([(s, e) for s, e, _ in evs], lo, hi)
        j = 0
        for gs, ge in idle:
            covered = 0.0
            while j < len(inner) and inner[j][1] <= gs:
                j += 1
            i = j
            while i < len(inner) and inner[i][0] < ge:
                s, e, name = inner[i]
                d = min(e, ge) - max(s, gs)
                if d > 0:
                    tot[name] += d
                    covered += d
                i += 1
            if ge - gs > covered:
                tot["outside"] += ge - gs - covered
    return [(n, v / 1e9) for n, v in
            sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:k]]


def union_named(spans: Sequence[Tuple[float, float, str]]
                ) -> List[Tuple[float, float, str]]:
    """Spans sorted by start, each clipped to begin where the one before
    it ended, so that no instant is counted twice."""
    out: List[Tuple[float, float, str]] = []
    at = float("-inf")
    for s, e, name in sorted(spans):
        s = max(s, at)
        if e > s:
            out.append((s, e, name))
            at = e
    return out
