"""End to end: seconds per job over the whole window, from its start to
the end of the last job, over the jobs completed in it."""


def read(run):
    jobs = run.loop.jobs
    if not jobs:
        return None
    return (jobs[-1].end - run.window_start) / len(jobs)
