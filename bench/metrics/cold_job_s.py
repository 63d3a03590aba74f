"""Compile: mean seconds of the first job on each of the run's graphs, a
graph the process has not seen, run in set-up: build, route, forest and
queries as in the window, with every compile the new graph brings."""


def read(run):
    cold = run.loop.cold_jobs
    if not cold:
        return None
    return sum(j.end - j.start for j in cold) / len(cold)
