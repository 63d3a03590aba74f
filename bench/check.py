"""The comparison that decides ``correct``: each job's answers against the
plain reference (``reference.py``) of the same graph.

Every number is a count of answers that differ, so every limit is 0:

* ``incidence_mismatch``: r-clique rows, s-cliques (as vertex triples of
  their three r-cliques), ``deg0`` entries and membership-CSR pairs that
  differ from the reference's incidence;
* ``core_mismatch``: r-cliques whose core number differs;
* ``rounds_mismatch``: the gap between the job's round count and the
  reference peel's;
* ``partition_mismatch`` (jobs that build the forest): r-cliques whose
  nucleus differs, summed over every level of the hierarchy, read from
  the forest the job built;
* ``nuclei_mismatch`` (jobs that query): nuclei answered at the job's
  query levels that differ from the reference's (vertex set, r-clique
  count, edges inside), plus levels queried that the reference would not.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

import reference

LIMITS = {"incidence_mismatch": 0, "core_mismatch": 0, "rounds_mismatch": 0,
          "partition_mismatch": 0, "nuclei_mismatch": 0}


def _sym_diff(a: np.ndarray, b: np.ndarray) -> int:
    a, b = np.unique(a), np.unique(b)
    return int(np.setdiff1d(a, b).size + np.setdiff1d(b, a).size)


def incidence_mismatch(problem, ref: reference.Incidence) -> int:
    rc = np.asarray(problem.r_cliques, np.int64).reshape(-1, 2)
    inc = np.asarray(problem.inc_rid, np.int64).reshape(-1, 3)
    deg0 = np.asarray(problem.deg0, np.int64)
    off = np.asarray(problem.mem_offsets, np.int64)
    sids = np.asarray(problem.mem_sids, np.int64)
    n = ref.n
    bad = 0
    same_r = rc.shape == ref.edges.shape and np.array_equal(rc, ref.edges)
    if not same_r:
        bad += _sym_diff(rc[:, 0] * n + rc[:, 1],
                         ref.edges[:, 0] * n + ref.edges[:, 1]) or 1
    # s-cliques: the six endpoints of three r-cliques of a triangle a<b<c
    # sort to a,a,b,b,c,c
    ok_ids = ((inc >= 0) & (inc < rc.shape[0])).all(axis=1)
    ends = np.sort(rc[np.clip(inc, 0, max(rc.shape[0] - 1, 0))]
                   .reshape(-1, 6), axis=1) if rc.shape[0] else \
        np.zeros((inc.shape[0], 6), np.int64)
    pattern = ok_ids & (ends[:, 0] == ends[:, 1]) & \
        (ends[:, 2] == ends[:, 3]) & (ends[:, 4] == ends[:, 5]) & \
        (ends[:, 1] < ends[:, 2]) & (ends[:, 3] < ends[:, 4])
    bad += int((~pattern).sum())
    tri = ends[pattern][:, ::2]
    key = lambda t: (t[:, 0] * n + t[:, 1]) * n + t[:, 2]
    bad += _sym_diff(key(tri), key(ref.triangles))
    bad += int(inc.shape[0] != np.unique(key(tri)).size)  # repeated rows
    if same_r and deg0.shape == ref.deg0.shape:
        bad += int((deg0 != ref.deg0).sum())
    else:
        bad += int(ref.deg0.size)
    # membership CSR: (r-clique, s-clique) pairs against the incidence rows
    if off.shape[0] == rc.shape[0] + 1 and off[-1] == sids.shape[0]:
        csr_r = np.repeat(np.arange(rc.shape[0]), np.diff(off))
        m = max(inc.shape[0], 1)
        bad += _sym_diff(csr_r * m + sids,
                         inc.reshape(-1) * m + np.repeat(
                             np.arange(inc.shape[0]), 3))
    else:
        bad += int(ref.n_s * 3)
    return bad


class GraphReference:
    """The reference's answers for one graph, computed on first use."""

    def __init__(self, n: int, edges: np.ndarray):
        self.inc = reference.incidence(n, edges)
        self.core, self.rounds = reference.peel(self.inc)
        self._parts: Dict[int, np.ndarray] = {}

    def partition(self, c: int) -> np.ndarray:
        if c not in self._parts:
            self._parts[c] = reference.partition(self.inc, self.core, c)
        return self._parts[c]


def _program_nuclei(nuclei: Dict) -> List:
    out = []
    for nu in nuclei.values():
        k = len(nu.vertices)
        pairs = k * (k - 1) / 2
        inside = int(round(nu.density * pairs)) if pairs else 0
        out.append((tuple(int(v) for v in nu.vertices),
                    int(nu.n_r_cliques), inside))
    return sorted(out)


def check_job(job, ref: GraphReference, traffic: Dict) -> Dict[str, int]:
    """Mismatch counts of one finished job."""
    out = {"incidence_mismatch": incidence_mismatch(job.problem, ref.inc)}
    core = np.asarray(job.core, np.int64)
    out["core_mismatch"] = int((core != ref.core).sum()) \
        if core.shape == ref.core.shape else int(ref.core.size)
    out["rounds_mismatch"] = abs(int(job.rounds) - int(ref.rounds))
    if traffic.get("tree"):
        bad = 0
        cs = sorted(set(reference.levels(ref.core))
                    | set(reference.levels(core)))
        for c in cs:
            got = reference.canonical_labels(job.tree.ancestor_at_level(c))
            want = ref.partition(c)
            bad += int((got != want).sum()) if got.shape == want.shape \
                else int(want.size)
        out["partition_mismatch"] = bad
    if traffic.get("query_levels"):
        want_levels = reference.query_levels(
            ref.core, traffic["query_levels"])
        bad = len(set(job.answers) ^ set(want_levels))
        for c in want_levels:
            want = reference.nuclei(ref.inc, ref.partition(c))
            got = _program_nuclei(job.answers[c]) if c in job.answers else []
            bad += len(set(got) ^ set(want))
        out["nuclei_mismatch"] = bad
    return out
