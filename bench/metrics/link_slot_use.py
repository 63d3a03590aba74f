"""Peel engine: the share of the link fixpoint's worklist slots that held
a live link, in percent: 100 * live_links / link_slots, summed over the
window's engine calls (the ``repro.engine`` spans' arguments).
``link_slots`` is generations times the worklist's width, n_s * C links
plus n_r handoffs, of the padded shapes the engine ran."""
import program_trace


def read(run):
    prog = program_trace.of(run)
    if prog is None:
        return None
    lo, hi = program_trace.window(run.trace)
    slots = program_trace.engine_sum(prog, "link_slots", lo, hi)
    if slots <= 0:
        return None
    return 100.0 * program_trace.engine_sum(prog, "live_links", lo,
                                            hi) / slots
