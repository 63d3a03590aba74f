"""The program's own spans, named scopes and counters in a profiler trace.

The program (``repro.core.trace``) opens host spans named ``repro.*`` at
its layer boundaries, with counts as their arguments, and labels device
code with ``jax.named_scope`` (``repro.peel_round``,
``repro.round_links``, ``repro.link_fixpoint``, ``repro.uf_union``).
``load_program`` reads a trace file into a ``ProgramTrace``:

* ``spans``: every ``repro.*`` host event, with its arguments;
* ``ops``: each device plane's ops, with the scope path of each: the
  ``repro.*`` components of the op's ``tf_op`` (``xspace.py`` reads it
  from the event metadata; the plane's ``XLA Modules`` line names the
  program of each op).

``scope_time`` splits device time among scope paths, each instant
counted once: synchronous ops (the ``XLA Ops`` line) nest in time, a
``while`` inside the ops its body runs, and an instant belongs to the
innermost op running then.  An op without ``tf_op`` (a ``while``, for
one) takes the longest scope path that all the ops nested in it share,
so the link fixpoint's own ``while`` counts as ``repro.link_fixpoint``
and the peel loop's, which holds ops of several scopes, as none.  An
instant in which only asynchronous copies (``Async XLA Ops``) run counts
under the copy's own path, none where its metadata has no ``tf_op``.

The harness's ``tracing.load`` keeps the ``bench.*`` spans and HLO names
only, and the trace file is gone once it returns.  Importing this module
(every reader of a program-trace metric does) wraps ``tracing.load`` so
that the ``Trace`` it returns also carries ``program``, a
``ProgramTrace`` of the same file, leaving every field it had as it was;
the wrapper also logs two breakdowns of the traced window, device
seconds by innermost scope and idle seconds by innermost program span.
It also registers a listener for jax's lowering events that counts the
programs lowered under each program span, by request; the wrapper logs
those counts too.  With a program that has no spans or scopes, every
reader finds nothing and returns None.
"""
from __future__ import annotations

import collections
import dataclasses
import json
from typing import Dict, List, Optional, Sequence, Tuple

import tracing
import xspace

PREFIX = "repro."
ENGINE = "repro.engine"
Path = Tuple[str, ...]  # the repro.* scopes an op runs in, outermost first
OTHER = "other"         # device time under no scope of the program
# jax's event for each program lowered (compiled or loaded from the cache)
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@dataclasses.dataclass
class Op:
    start: float
    end: float
    path: Optional[Path]    # None: the op's metadata has no tf_op
    sync: bool              # on the XLA Ops line (else Async XLA Ops)


@dataclasses.dataclass
class ProgramTrace:
    # device plane name -> ops, sorted by start
    ops: Dict[str, List[Op]]
    # [(start_ns, end_ns, name, args)] of the program's host spans
    spans: List[Tuple[float, float, str, Dict[str, object]]]
    # scope_time's results, by windows
    times: Dict[Tuple[tracing.Interval, ...], Dict[Path, float]] = \
        dataclasses.field(default_factory=dict, repr=False)

    def span_intervals(self, name: str, lo: float = float("-inf"),
                       hi: float = float("inf")) -> List[tracing.Interval]:
        """The spans named ``name`` that start in ``[lo, hi]``."""
        return [(s, e) for s, e, n, _ in self.spans
                if n == name and lo <= s <= hi]


def scope_path(tf_op: Optional[str]) -> Optional[Path]:
    """``jit(f)/while/body/repro.a/while/body/repro.b/add:`` ->
    ``("repro.a", "repro.b")``; None for no ``tf_op``."""
    if tf_op is None:
        return None
    return tuple(p for p in (c.split(":", 1)[0] for c in tf_op.split("/"))
                 if p.startswith(PREFIX))


def _program_id(module_name: str) -> Optional[int]:
    """``jit_f(123)`` -> 123."""
    head, _, tail = module_name.rpartition("(")
    try:
        return int(tail.rstrip(")")) if head else None
    except ValueError:
        return None


def load_program(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData
    meta = xspace.device_op_metadata(path, tracing.DEVICE_PLANE_PREFIX)
    data = ProfileData.from_file(path)
    ops: Dict[str, List[Op]] = {}
    spans = []
    for plane in data.planes:
        if plane.name.startswith(tracing.DEVICE_PLANE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns,
                 _program_id(ev.name))
                for ev in (lines["XLA Modules"].events
                           if "XLA Modules" in lines else ()))
            plane_meta = meta.get(plane.name, {})
            out = ops.setdefault(plane.name, [])
            for line_name, sync in zip(tracing.OPS_LINES, (True, False)):
                if line_name not in lines:
                    continue
                evs = sorted(((ev.start_ns, ev.duration_ns, ev.name)
                              for ev in lines[line_name].events),
                             key=lambda t: t[0])
                m = 0
                for start, dur, name in evs:
                    while m + 1 < len(modules) and modules[m + 1][0] <= start:
                        m += 1
                    pid = modules[m][2] if modules and \
                        modules[m][0] <= start < modules[m][1] else None
                    op = plane_meta.get((pid, name))
                    path_ = scope_path(op.tf_op) if op is not None else None
                    out.append(Op(start, start + dur,
                                  path_ if sync or path_ is not None
                                  else (), sync))
            out.sort(key=lambda o: (o.start, -o.end))
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name, dict(ev.stats)))
    spans.sort(key=lambda t: (t[0], -t[1]))
    return ProgramTrace(ops=ops, spans=spans)


def _common(a: Optional[Path], b: Path) -> Path:
    if a is None:
        return b
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return a[:n]


class _Windows:
    """Sorted disjoint intervals; ``overlap`` of ever later intervals."""

    def __init__(self, windows: Sequence[tracing.Interval]):
        self.w = tracing.union(windows)
        self.i = 0

    def overlap(self, s: float, e: float) -> float:
        w, total = self.w, 0.0
        while self.i < len(w) and w[self.i][1] <= s:
            self.i += 1
        j = self.i
        while j < len(w) and w[j][0] < e:
            total += max(0.0, min(e, w[j][1]) - max(s, w[j][0]))
            j += 1
        return total


def plane_scope_time(ops: Sequence[Op], windows: Sequence[tracing.Interval]
                     ) -> Dict[Path, float]:
    """Nanoseconds of one device plane inside ``windows`` by scope path,
    each instant counted once (the module docstring says how).  An op
    with no ``tf_op`` and none inside it with one (a copy the compiler
    inserted) takes the path of the op it runs in."""
    sync = [o for o in ops if o.sync]
    n = len(sync)
    end = [o.end for o in sync]
    path: List[Optional[Path]] = [o.path for o in sync]
    shared: List[Optional[Path]] = [None] * n   # common path of children
    parent = [-1] * n
    own = [0.0] * n                              # ns as innermost op
    win = _Windows(windows)
    stack: List[int] = []
    at = 0.0

    def close() -> None:
        nonlocal at
        j = stack.pop()
        own[j] += win.overlap(at, end[j])
        at = end[j]
        if path[j] is None:
            path[j] = shared[j]
        if stack and path[j] is not None:
            shared[stack[-1]] = _common(shared[stack[-1]], path[j])

    for i, o in enumerate(sync):
        while stack and end[stack[-1]] <= o.start:
            close()
        if stack:
            own[stack[-1]] += win.overlap(at, o.start)
            # an op that outlasts its enclosing op is cut at its end
            end[i] = min(end[i], end[stack[-1]])
            parent[i] = stack[-1]
        at = o.start
        stack.append(i)
    while stack:
        close()
    out: Dict[Path, float] = collections.Counter()
    for j in range(n):  # an enclosing op comes before the ops inside it
        if path[j] is None:
            path[j] = path[parent[j]] if parent[j] >= 0 else ()
        out[path[j]] += own[j]
    # asynchronous copies, where no synchronous op runs
    busy = tracing.union([(o.start, o.end) for o in sync])
    win = _Windows(windows)
    covered = float("-inf")
    k = 0
    for o in ops:
        if o.sync or o.end <= max(o.start, covered):
            continue
        at = max(o.start, covered)
        covered = o.end
        while k < len(busy) and busy[k][1] <= at:
            k += 1
        j = k
        while j < len(busy) and busy[j][0] < o.end:
            if busy[j][0] > at:
                out[o.path] += win.overlap(at, busy[j][0])
            at = max(at, busy[j][1])
            j += 1
        if o.end > at:
            out[o.path] += win.overlap(at, o.end)
    return {k: v for k, v in out.items() if v > 0}


def scope_time(pt: ProgramTrace, windows: Sequence[tracing.Interval]
               ) -> Dict[Path, float]:
    """``plane_scope_time`` averaged over the device planes."""
    key = tuple(windows)
    if key not in pt.times:
        out: Dict[Path, float] = collections.Counter()
        for ops in pt.ops.values():
            for k, v in plane_scope_time(ops, windows).items():
                out[k] += v / len(pt.ops)
        pt.times[key] = out
    return pt.times[key]


def time_under(times: Dict[Path, float], scope: str) -> float:
    """Nanoseconds of the paths that pass through ``scope``."""
    return sum(v for k, v in times.items() if scope in k)


def device_scopes(pt: ProgramTrace, lo: float, hi: float
                  ) -> List[Tuple[str, float]]:
    """Device seconds in ``[lo, hi]`` by innermost scope (``other``: none
    of the program's), summed over devices."""
    out: Dict[str, float] = collections.Counter()
    for ops in pt.ops.values():
        for k, v in plane_scope_time(ops, [(lo, hi)]).items():
            out[k[-1] if k else OTHER] += v
    return [(n, v / 1e9) for n, v in
            sorted(out.items(), key=lambda kv: (-kv[1], kv[0]))]


def innermost_spans(spans: Sequence[Tuple[float, float, str]]
                    ) -> List[Tuple[float, float, str]]:
    """Disjoint segments, each named by the innermost of the nested
    ``spans`` open then."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []
    at = float("-inf")
    for s, e, name in sorted(spans, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if end > at:
                out.append((at, end, top))
                at = end
        if stack and s > at:
            out.append((at, s, stack[-1][1]))
        at = max(at, s)
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    while stack:
        end, top = stack.pop()
        if end > at:
            out.append((at, end, top))
            at = end
    return out


def idle_by_program_span(trace: tracing.Trace, pt: ProgramTrace,
                         lo: float, hi: float) -> List[Tuple[str, float]]:
    """Idle device seconds in ``[lo, hi]`` by the innermost ``repro.*``
    span the host was in, else the harness span (``tracing.
    idle_by_host_span``'s), else ``outside``; summed over devices."""
    program = innermost_spans([(s, e, n) for s, e, n, _ in pt.spans])
    own = tracing.union([(s, e) for s, e, _ in program])
    bench = tracing.union_named([sp for sp in trace.spans
                                 if sp[2] != "bench.window"])
    labelled = list(program)
    for s, e, name in bench:
        labelled += [(a, b, name) for a, b in tracing.gaps(own, s, e)]
    labelled.sort()
    tot: Dict[str, float] = collections.Counter()
    for evs in trace.ops.values():
        j = 0
        for gs, ge in tracing.gaps([(s, e) for s, e, _ in evs], lo, hi):
            covered = 0.0
            while j < len(labelled) and labelled[j][1] <= gs:
                j += 1
            i = j
            while i < len(labelled) and labelled[i][0] < ge:
                s, e, name = labelled[i]
                d = min(e, ge) - max(s, gs)
                if d > 0:
                    tot[name] += d
                    covered += d
                i += 1
            if ge - gs > covered:
                tot["outside"] += ge - gs - covered
    return [(n, v / 1e9) for n, v in
            sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))]


def engine_sum(pt: ProgramTrace, key: str, lo: float, hi: float) -> int:
    """The sum of argument ``key`` over the ``repro.engine`` spans that
    start in ``[lo, hi]`` (the counts the engine returned)."""
    return sum(int(a.get(key, 0)) for s, _, n, a in pt.spans
               if n == ENGINE and lo <= s <= hi)


def engine_time(run) -> Optional[Tuple[Dict[Path, float], float]]:
    """(device ns by scope path, device-busy ns) inside the traced
    window's ``repro.engine`` spans, averaged over devices; None where
    the window has none."""
    prog = of(run)
    if prog is None:
        return None
    engine = prog.span_intervals(ENGINE, *window(run.trace))
    busy = tracing.busy_inside(run.trace, engine)
    if busy <= 0:
        return None
    return scope_time(prog, engine), busy


def window(trace: tracing.Trace) -> tracing.Interval:
    """The harness's window span, else the whole trace."""
    win = tracing.span_intervals(trace, "bench.window")
    if win:
        return win[0]
    return (min(s for s, _, _ in trace.spans),
            max(e for _, e, _ in trace.spans))


def of(run) -> Optional[ProgramTrace]:
    """The run's program trace, None when there is none."""
    return getattr(run.trace, "program", None) if run.trace else None


class ProgramsBySpan:
    """Programs lowered (jax's lowering events), counted by the request
    and the innermost program span they were lowered under.  A lowering
    outside any request's spans counts for the next request, the one a
    build outside the router is for."""

    def __init__(self):
        self.counts: Dict[Tuple[int, str], int] = collections.Counter()
        self._program = None

    def on_event(self, event: str, duration: float, **_) -> None:
        if event != LOWERING:
            return
        if self._program is None:
            try:
                from repro.core import trace as program
            except ImportError:  # a program without spans
                program = False
            self._program = program
        if not self._program:
            self.counts[(0, "outside")] += 1
            return
        rid = self._program.current_request() or \
            self._program.last_request() + 1
        self.counts[(rid, self._program.current() or "outside")] += 1

    def lines(self) -> List[str]:
        by_request: Dict[int, Dict[str, int]] = collections.defaultdict(dict)
        for (rid, name), n in sorted(self.counts.items()):
            by_request[rid][name] = n
        return [f"programs: request={rid} total={sum(c.values())} "
                + json.dumps(c) for rid, c in sorted(by_request.items())]


PROGRAMS = ProgramsBySpan()


def _install() -> None:
    if getattr(tracing.load, "with_program", False):
        return
    import jax
    jax.monitoring.register_event_duration_secs_listener(PROGRAMS.on_event)
    plain = tracing.load

    def load_with_program(path: str) -> tracing.Trace:
        trace = plain(path)
        trace.program = load_program(path)
        lo, hi = window(trace)
        print("device_scopes: " + json.dumps(
            device_scopes(trace.program, lo, hi)), flush=True)
        print("idle_gaps_program: " + json.dumps(
            idle_by_program_span(trace, trace.program, lo, hi)), flush=True)
        for line in PROGRAMS.lines():
            print(line, flush=True)
        return trace

    load_with_program.with_program = True
    tracing.load = load_with_program


_install()
