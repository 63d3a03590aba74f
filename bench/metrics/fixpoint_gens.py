"""Peel engine: link-fixpoint generations per peel round: the engine's
``fixpoint_gens`` counter (the fixpoint loop's own generation count,
summed over the rounds) over its rounds, summed over the window's engine
calls (the ``repro.engine`` spans' arguments)."""
import program_trace


def read(run):
    prog = program_trace.of(run)
    if prog is None:
        return None
    lo, hi = program_trace.window(run.trace)
    gens = program_trace.engine_sum(prog, "fixpoint_gens", lo, hi)
    rounds = program_trace.engine_sum(prog, "rounds", lo, hi)
    if gens <= 0 or rounds <= 0:
        return None
    return gens / rounds
