"""Peel engine: milliseconds per peel round, from the harness's span
around ``Router.route`` less the compile seconds jax reported inside it,
over the rounds the jobs took (``Decomposition.rounds``)."""


def read(run):
    jobs = run.loop.jobs
    rounds = sum(j.rounds for j in jobs)
    if rounds <= 0:
        return None
    busy = sum(j.route_s - j.route_compile_s for j in jobs)
    return 1000.0 * busy / rounds
