"""Concurrent request intake: bounded queue, coalescing, admission.

Concurrent clients and a single-writer engine meet here.  The
``Frontend`` owns a bounded queue and ONE worker thread; clients call
``submit()`` (any thread) and get a ``concurrent.futures.Future``, the
worker drains the queue and is the only thread that touches the
``Router``'s Sessions — so concurrent submissions produce artifacts
bit-identical to serial execution (the parity tests pin this), with no
engine-level locking at all.

  * **Admission control.**  ``submit()`` resolves the request's problem
    and computes the *padded* plan-budget estimate — the same
    ``session.padded_plan_bytes`` the Session's plan gate uses — and
    rejects over-budget graphs up front with a typed
    ``AdmissionError`` carrying the computed bytes.  A full queue is a
    typed ``QueueFullError`` (backpressure, not silent buffering).
  * **Coalescing.**  The worker drains whatever is queued, groups
    decompose jobs by (pool, shape bucket), and runs each group through
    ``Session.decompose_many`` — same-bucket tenants submitted together
    ride one warm executable back-to-back instead of interleaving pool
    switches.  Updates to named artifacts keep FIFO order (per-artifact
    generations must apply in submission order).
  * **Queries stay lock-free.**  ``query()`` reads the named artifact's
    cached cut/nuclei tables directly — the high-qps path never enters
    the queue.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Tuple

from ..core.incidence import NucleusProblem
from ..core.session import PLAN_BUDGET_BYTES, padded_plan_bytes
from .router import Request, Router, canonical_config, pool_key


class AdmissionError(RuntimeError):
    """Request rejected up front: the padded engine plan for this graph
    would exceed the server's admission budget."""

    def __init__(self, plan_bytes: int, budget_bytes: int):
        self.plan_bytes = int(plan_bytes)
        self.budget_bytes = int(budget_bytes)
        super().__init__(
            f"admission rejected: padded plan needs {self.plan_bytes} "
            f"bytes > budget {self.budget_bytes} bytes — decompose this "
            f"graph offline (sharded/chunked) and serve the artifact, or "
            f"raise admission_budget_bytes")


class QueueFullError(RuntimeError):
    """Request rejected: the bounded intake queue is full (backpressure —
    retry after the pool drains)."""


@dataclasses.dataclass
class _Job:
    request: Request
    future: Future
    problem: Optional[NucleusProblem]   # resolved at admission time
    pool: Optional[Tuple]               # pool key (decompose jobs)
    bucket: Optional[Tuple]             # shape-bucket key (decompose jobs)


class Frontend:
    """The server's intake: ``submit() -> Future`` + a worker loop.

    ``max_queue`` bounds in-flight work (admission is per-graph, the
    queue bound is per-server); ``admission_budget_bytes`` defaults to
    the Session's plan budget (``session.PLAN_BUDGET_BYTES``).
    ``start()``/``stop()`` manage
    the worker thread; ``stop()`` drains nothing — queued futures are
    cancelled so shutdown is prompt and explicit.
    """

    def __init__(self, router: Optional[Router] = None, *,
                 max_queue: int = 64,
                 admission_budget_bytes: int = PLAN_BUDGET_BYTES,
                 batch_wait_s: float = 0.002):
        self.router = router if router is not None else Router()
        self.admission_budget_bytes = int(admission_budget_bytes)
        self.batch_wait_s = float(batch_wait_s)
        self._queue: "queue.Queue[_Job]" = queue.Queue(maxsize=max_queue)
        self._stats_lock = threading.Lock()
        self.stats: Dict[str, int] = {
            "submitted": 0,           # accepted into the queue
            "served": 0,              # futures resolved successfully
            "failed": 0,              # futures resolved with an exception
            "rejected_admission": 0,  # AdmissionError at submit()
            "rejected_queue": 0,      # QueueFullError at submit()
            "batches": 0,             # worker drain cycles that did work
            "coalesced": 0,           # decompose jobs served in a shared
                                      # decompose_many batch (size >= 2)
        }
        self._worker: Optional[threading.Thread] = None
        self._running = threading.Event()

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "Frontend":
        if self._worker is not None:
            return self
        self._running.set()
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="nucleus-frontend")
        self._worker.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        if self._worker is None:
            return
        self._running.clear()
        self._worker.join(timeout)
        self._worker = None
        # cancel anything still queued: shutdown must be explicit, not
        # silently half-served
        while True:
            try:
                job = self._queue.get_nowait()
            except queue.Empty:
                break
            job.future.cancel()

    @property
    def queue_depth(self) -> int:
        return self._queue.qsize()

    def _count(self, name: str, by: int = 1) -> None:
        with self._stats_lock:
            self.stats[name] += by

    # -- intake ------------------------------------------------------------
    def submit(self, request: Request) -> "Future":
        """Admit + enqueue one request; returns a Future resolving to its
        ``Decomposition``.  Raises ``AdmissionError`` (over-budget graph)
        or ``QueueFullError`` (backpressure) instead of queueing doomed
        or unbounded work."""
        if self._worker is None:
            raise RuntimeError("Frontend not started — call start() first")
        problem = pool = bucket = None
        if request.kind == "decompose":
            problem, config = self.router.resolve(request)
            need = padded_plan_bytes(problem)
            if need > self.admission_budget_bytes:
                self._count("rejected_admission")
                raise AdmissionError(need, self.admission_budget_bytes)
            pool = pool_key(config)
            sess = self.router.pool(config)
            bucket = sess.bucket_key(problem, config)
        fut: Future = Future()
        job = _Job(request=request, future=fut, problem=problem,
                   pool=pool, bucket=bucket)
        try:
            self._queue.put_nowait(job)
        except queue.Full:
            self._count("rejected_queue")
            raise QueueFullError(
                f"intake queue full ({self._queue.maxsize} jobs) — "
                f"retry after the pool drains") from None
        self._count("submitted")
        return fut

    def submit_wait(self, request: Request, timeout: float = 300.0):
        """``submit`` + block for the artifact (small-scale callers)."""
        return self.submit(request).result(timeout=timeout)

    # -- reads (never queued) ----------------------------------------------
    def query(self, name: str, kind: str, c: int):
        """Answer a cut/nuclei query from the named live artifact's
        cached tables — the decompose-once/query-many hot path."""
        dec = self.router.artifact(name)
        if kind == "cut":
            return dec.cut(int(c))
        if kind == "nuclei":
            return dec.nuclei(int(c))
        raise ValueError(f"unknown query kind {kind!r}; expected "
                         f"'cut' or 'nuclei'")

    # -- the worker --------------------------------------------------------
    def _run(self) -> None:
        while self._running.is_set():
            try:
                first = self._queue.get(timeout=0.05)
            except queue.Empty:
                continue
            batch = [first]
            # drain whatever arrived with it (plus a short window so a
            # burst of concurrent submits lands in one coalesced batch)
            deadline_waited = False
            while True:
                try:
                    batch.append(self._queue.get_nowait())
                except queue.Empty:
                    if deadline_waited or not self.batch_wait_s:
                        break
                    time.sleep(self.batch_wait_s)
                    deadline_waited = True
            self._serve_batch(batch)
            self._count("batches")

    def _serve_batch(self, batch: List[_Job]) -> None:
        # decompose jobs grouped by (pool, shape bucket): each group is
        # one decompose_many call on one warm Session — the coalescing
        # claim.  Updates run afterwards in FIFO order (publication
        # precedes update within one drain; per-artifact generations
        # stay ordered).
        groups: Dict[Tuple, List[_Job]] = {}
        updates: List[_Job] = []
        for job in batch:
            if job.request.kind == "update":
                updates.append(job)
            else:
                groups.setdefault((job.pool, job.bucket), []).append(job)
        for (_pool, _bucket), jobs in groups.items():
            try:
                decs = self.router.route_many(
                    [j.request for j in jobs],
                    problems=[j.problem for j in jobs])
            except Exception as e:
                for j in jobs:
                    j.future.set_exception(e)
                self._count("failed", len(jobs))
                continue
            for j, dec in zip(jobs, decs):
                j.future.set_result(dec)
            self._count("served", len(jobs))
            if len(jobs) >= 2:
                self._count("coalesced", len(jobs))
        for job in updates:
            try:
                dec = self.router.update(job.request.artifact,
                                         job.request.update)
            except Exception as e:
                job.future.set_exception(e)
                self._count("failed")
                continue
            job.future.set_result(dec)
            self._count("served")
