"""Peel engine: the least bytes the exact peel must move
(``cost.peel_least_bytes`` of each job's unpadded shapes) at the chip's
HBM bandwidth (``peaks.json``), over the device-busy time inside the
harness's route spans in the profiler trace, in percent."""
from cost import peel_least_bytes


def read(run):
    if run.trace is None or not run.loop.jobs:
        return None
    busy_s = run.trace_busy_in("bench.route") / 1e9
    if busy_s <= 0:
        return None
    least = sum(peel_least_bytes(j.n_r, j.n_s, j.C) for j in run.loop.jobs)
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / busy_s
