"""Peel engine: device milliseconds per peel round in the program's
``repro.peel_round`` scope (the round body: select, dead test, decrement),
inside the window's ``repro.engine`` spans, each instant counted once
(``program_trace.scope_time``), over the rounds those engine calls
returned (their spans' ``rounds``)."""
import program_trace


def read(run):
    times = program_trace.engine_time(run)
    if times is None:
        return None
    rounds = program_trace.engine_sum(program_trace.of(run), "rounds",
                                      *program_trace.window(run.trace))
    ns = program_trace.time_under(times[0], "repro.peel_round")
    if rounds <= 0 or ns <= 0:
        return None
    return ns / 1e6 / rounds
