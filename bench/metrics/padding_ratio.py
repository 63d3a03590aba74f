"""Peel engine: rows the engine ran over the rows of the job, the shape
bucket's padding: (n_s_pad + n_r_pad) / (n_s + n_r), the padded and the
real s-cliques (incidence rows) and r-cliques, summed over the window's
engine calls (the ``repro.engine`` spans' arguments).  Every peel round
passes the padded rows, so the ratio bounds the round's wasted work."""
import program_trace


def read(run):
    prog = program_trace.of(run)
    if prog is None:
        return None
    lo, hi = program_trace.window(run.trace)
    real = sum(program_trace.engine_sum(prog, k, lo, hi)
               for k in ("n_s", "n_r"))
    if real <= 0:
        return None
    return sum(program_trace.engine_sum(prog, k, lo, hi)
               for k in ("n_s_pad", "n_r_pad")) / real
