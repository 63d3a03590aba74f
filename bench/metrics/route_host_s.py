"""Peel engine: mean seconds per job of the program's ``repro.route``
span not covered by its ``repro.engine`` span: the host's part of the
route (problem adoption, plan, bucket lookup, padding, scatter plan,
the ``Decomposition``)."""
import program_trace
import tracing


def read(run):
    prog = program_trace.of(run)
    if prog is None:
        return None
    lo, hi = program_trace.window(run.trace)
    routes = prog.span_intervals("repro.route", lo, hi)
    if not routes:
        return None
    engine = prog.span_intervals(program_trace.ENGINE, lo, hi)
    host = sum(e - s for s, e in routes) - tracing.overlap(routes, engine)
    return host / 1e9 / len(routes)
