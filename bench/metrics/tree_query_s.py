"""Hierarchy: mean seconds per job of ``.tree`` plus ``.cut``/``.nuclei``
at the traffic's query levels, from the harness's span around them."""


def read(run):
    jobs = run.loop.jobs
    if not run.loop.traffic.get("tree") or not jobs:
        return None
    return sum(j.tree_query_s for j in jobs) / len(jobs)
