"""Host incidence build (``repro.core.build_problem``): mean seconds per
job of the harness's span around it."""


def read(run):
    jobs = run.loop.jobs
    return sum(j.build_s for j in jobs) / len(jobs) if jobs else None
