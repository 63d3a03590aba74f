"""Peel engine: the link fixpoint's share of the device-busy time inside
the window's ``repro.engine`` spans, in percent.  The fixpoint's time is
that of the ops in the ``repro.link_fixpoint`` scope (the union-find's
``repro.uf_union`` inside it included) and of its own ``while`` ops,
which carry no scope and are found by the ops nested in them
(``program_trace.scope_time``); each instant counts once."""
import program_trace


def read(run):
    times = program_trace.engine_time(run)
    if times is None:
        return None
    ns = program_trace.time_under(times[0], "repro.link_fixpoint")
    if ns <= 0:
        return None
    return 100.0 * ns / times[1]
