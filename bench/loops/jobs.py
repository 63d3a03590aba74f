"""The closed loop of graph jobs: one client submits a graph, waits for
its answers, and submits the next.

A traffic mix names this loop with ``"loop": "jobs"`` and gives:

* ``graphs``: how many graphs the window cycles over.  Graph ``i`` is
  base graph ``i``, drawn by the configuration's generator from the
  stream ``(base_seed, i)``, with its vertex ids permuted at random from
  ``(--seed, i)``, as Graph500's generator ends.  Every seed so hands the
  program other edge lists of the same sizes and the same peel rounds:
  Kronecker graphs of one scale differ in rounds by 10-20%, and a window
  of new graphs would time the seed's draw, not the program;
* ``base_seed``: the stream of the base graphs;
* ``tree``: whether a job builds the forest (``.tree``);
* ``query_levels``: the levels each job answers ``.cut``/``.nuclei`` at:
  ``"lowest"`` (the least positive core number) or a fraction f of kmax.

A job hands a graph to the host build (``repro.core.build_problem``),
routes the problem through ``repro.serve.Router.route`` with the
configuration's request, and, where the mix asks, builds the forest and
answers the queries.  It ends when core numbers and answers are on the
host.

Set-up first runs one whole job on a fresh graph, drawn by the generator
from ``--seed`` alone: a graph of shapes the program has not seen, whose
compiles land in ``setup_s`` in every run with a new seed (the cold job;
it also compiles the Session bucket).  Then each window graph goes once
through the build and a route without the hierarchy, which compiles or
loads every shape it brings, and the cell's own engine is prewarmed for
the buckets they land in.  The window runs whole cycles over the
window graphs, each cycle in an order drawn from the seed, until its
seconds have passed; nothing is left to compile.  After the window every
job of the window, and the cold job, is compared with the plain
reference of its graph (``check.py``).
"""
from __future__ import annotations

import dataclasses
import time
import traceback
from typing import Any, Dict, Iterator, List, Optional

import check
import reference
from graphs import relabel, rng_for

# streams of --seed that no graph index uses
ORDER_STREAM = 1 << 32
FRESH_STREAM = 1 << 33


@dataclasses.dataclass
class HostIncidence:
    """Host copies of the incidence a job's build produced: all the check
    needs, so that no job's device arrays outlive the job."""

    r_cliques: Any
    inc_rid: Any
    deg0: Any
    mem_offsets: Any
    mem_sids: Any
    n_sub: int

    @classmethod
    def of(cls, problem) -> "HostIncidence":
        import numpy as np
        return cls(*(np.asarray(getattr(problem, k)) for k in (
            "r_cliques", "inc_rid", "deg0", "mem_offsets", "mem_sids")),
            n_sub=int(problem.n_sub))


@dataclasses.dataclass
class Job:
    graph: int              # index into the run's graphs
    problem: HostIncidence
    core: Any
    rounds: int
    bucket: Any             # (n_r_pad, n_s_pad) of its Session bucket
    tree: Any               # the forest the job built (None: no forest)
    answers: Dict[int, Any]
    build_s: float
    route_s: float
    route_compile_s: float
    tree_query_s: float
    compile_s: float        # compile seconds anywhere in the job
    compiles: int           # backend-compile events anywhere in the job
    programs: int           # programs lowered: compiled or cache loads
    start: float
    end: float

    @property
    def n_r(self) -> int:
        return int(self.problem.r_cliques.shape[0])

    @property
    def n_s(self) -> int:
        return int(self.problem.inc_rid.shape[0])

    @property
    def C(self) -> int:
        return self.problem.n_sub

    def describe(self) -> str:
        return (f"graph={self.graph} bucket={self.bucket} n_r={self.n_r} "
                f"n_s={self.n_s} rounds={self.rounds} "
                f"build_s={self.build_s:.4f} route_s={self.route_s:.4f} "
                f"tree_query_s={self.tree_query_s:.4f} "
                f"compile_s={self.compile_s:.4f} compiles={self.compiles} "
                f"programs={self.programs}")


def job_order(seed: int, graphs: int) -> Iterator[int]:
    """Graph indices for the window: cycles over the run's graphs, each in
    an order drawn from the seed."""
    rng = rng_for(seed, ORDER_STREAM)
    while True:
        yield from (int(i) for i in rng.permutation(graphs))


class Loop:
    """The jobs of one run, driven through the program."""

    def __init__(self, cell, seed: int, clock, log):
        from repro.core import build_problem
        from repro.graph.container import Graph
        from repro.serve import Request, Router
        self._build_problem, self._Graph = build_problem, Graph
        self._Request = Request
        self.cell, self.seed, self.clock, self.log = cell, seed, clock, log
        self.request = dict(cell.config["request"])
        self.build = cell.config["build"]
        self.traffic = cell.traffic
        self.router = Router()
        self.n_graphs = int(self.traffic["graphs"])
        self.fresh = self.n_graphs  # the index of the cold job's graph
        self.edges: Dict[int, Any] = {}
        self.n: Dict[int, int] = {}
        self.graphs: Dict[int, Any] = {}
        self.cold_jobs: List[Job] = []
        self.jobs: List[Job] = []
        self.attempted = 0
        self.failed = 0

    def make_graph(self, index: int) -> None:
        """Window graph ``index``: base graph ``index`` with its vertex ids
        permuted from ``(--seed, index)``; or, for ``self.fresh``, a new
        graph from ``--seed``."""
        import jax.numpy as jnp
        params = self.cell.config["graph"]
        if index == self.fresh:
            n, edges = self.cell.generator.graph(
                params, rng_for(self.seed, FRESH_STREAM))
        else:
            n, base = self.cell.generator.graph(
                params, rng_for(int(self.traffic["base_seed"]), index))
            edges = relabel(base, rng_for(self.seed, index).permutation(n))
        self.n[index], self.edges[index] = n, edges
        # the edges are canonical (unique, lo < hi, lexsorted), the form
        # ``repro.graph.make_graph`` returns
        self.graphs[index] = self._Graph(
            n=n, edges=jnp.asarray(edges, jnp.int32))

    def job(self, index: int, request: Optional[Dict[str, Any]] = None,
            queries: bool = True) -> Job:
        import jax
        import numpy as np
        from jax.profiler import TraceAnnotation
        clock = self.clock
        c_start, n_start = clock.seconds, clock.backend_compiles
        p_start = clock.lowerings
        t0 = time.perf_counter()
        request = request or self.request
        with TraceAnnotation("bench.build"):
            problem = self._build_problem(
                self.graphs[index], int(request["r"]), int(request["s"]),
                build=self.build)
            jax.block_until_ready((problem.inc_rid, problem.deg0,
                                   problem.mem_offsets, problem.mem_sids))
        t1 = time.perf_counter()
        c0 = clock.seconds
        with TraceAnnotation("bench.route"):
            dec = self.router.route(self._Request(graph=problem,
                                                  **request))
            core = np.asarray(dec.core)
        t2 = time.perf_counter()
        route_compile_s = clock.seconds - c0
        answers: Dict[int, Any] = {}
        tree = None
        if queries and self.traffic.get("tree"):
            with TraceAnnotation("bench.tree_query"):
                tree = dec.tree
                for c in reference.query_levels(
                        core, self.traffic["query_levels"]):
                    dec.cut(c)
                    answers[c] = dec.nuclei(c)
        t3 = time.perf_counter()
        return Job(graph=index, problem=HostIncidence.of(problem),
                   bucket=self.bucket(problem, request), core=core,
                   rounds=int(dec.rounds), tree=tree, answers=answers,
                   build_s=t1 - t0, route_s=t2 - t1,
                   route_compile_s=route_compile_s, tree_query_s=t3 - t2,
                   compile_s=clock.seconds - c_start,
                   compiles=clock.backend_compiles - n_start,
                   programs=clock.lowerings - p_start,
                   start=t0, end=t3)

    def session(self, request: Dict[str, Any]):
        """The Router's Session for ``request``'s configuration."""
        return self.router.pool(self._Request(**request).config())

    def bucket(self, problem, request: Dict[str, Any]):
        """(n_r_pad, n_s_pad) of the Session bucket ``problem`` lands in."""
        key = self.session(request).bucket_key(problem)
        return key[4], key[5]

    def setup(self) -> None:
        for i in range(self.n_graphs + 1):
            self.make_graph(i)
        j = self.job(self.fresh)
        self.cold_jobs.append(j)
        self.log(f"cold job: {j.describe()} job_s={j.end - j.start:.4f}")
        # a route without the hierarchy reaches every graph-sized shape of
        # the route at a fraction of a forest job's cost
        lean = dict(self.request, hierarchy="none")
        for i in range(self.n_graphs):
            j = self.job(i, request=lean, queries=False)
            self.log(f"warm pass: {j.describe()}")
        if lean != self.request:
            # the engine is keyed on each graph's bucket and scatter plan:
            # warm the cell's own engine for the buckets the warm pass saw,
            # through the program's restart path (``Session.prewarm`` runs
            # an all-ghost problem of the bucket's shapes)
            fused = self.request.get("hierarchy", "fused") == "fused"
            seen = self.session(lean).manifest()["buckets"]
            n = self.session(self.request).prewarm(
                [dict(e, fused=fused) for e in seen])
            self.log(f"prewarm: buckets={n} "
                     f"programs={self.clock.lowerings}")
        self._order = job_order(self.seed, self.n_graphs)

    def window(self, seconds: float) -> None:
        """Whole cycles over the run's graphs until ``seconds`` have
        passed."""
        t0 = time.perf_counter()
        while True:
            index = next(self._order)
            self.attempted += 1
            try:
                self.jobs.append(self.job(index))
            except Exception:  # a job that fails is counted, not fatal
                traceback.print_exc()
                self.failed += 1
            if self.attempted % self.n_graphs == 0 and \
                    time.perf_counter() - t0 >= seconds:
                break
        for k, j in enumerate(self.jobs):
            self.log(f"job {k}: {j.describe()}")

    def check(self) -> Dict[str, Dict[str, Any]]:
        """Every job of the window, and the cold job, against the reference
        of its graph."""
        refs: Dict[int, check.GraphReference] = {}
        totals = {k: 0 for k in check.LIMITS}
        names = ["incidence_mismatch", "core_mismatch", "rounds_mismatch"]
        if self.traffic.get("tree"):
            names.append("partition_mismatch")
        if self.traffic.get("query_levels"):
            names.append("nuclei_mismatch")
        t = time.perf_counter()
        for j in self.jobs + self.cold_jobs:
            if j.graph not in refs:
                refs[j.graph] = check.GraphReference(
                    self.n[j.graph], self.edges[j.graph])
            for k, v in check.check_job(j, refs[j.graph],
                                        self.traffic).items():
                totals[k] += v
        self.log(f"reference: graphs={len(refs)} "
                 f"jobs={len(self.jobs) + len(self.cold_jobs)} "
                 f"seconds={time.perf_counter() - t:.3f}")
        return {k: {"value": totals[k], "limit": check.LIMITS[k]}
                for k in names}
