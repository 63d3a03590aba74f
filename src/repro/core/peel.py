"""Parallel peeling: exact (ARB-NUCLEUS analog) and approximate (Alg. 2).

Two backends, one schedule (``repro.core.schedule.PeelSchedule``) and one
round body (``repro.core.engine.peel_round``):

  * ``gather``: each round touches only the s-cliques incident to the peeled
    set (CSR gather + unique + segment add) — the work-efficient formulation
    matching the paper's bounds; shapes are data-dependent per round, so this
    backend stays an eager host loop.
  * ``dense``: delegates to the compiled engine — every round is a
    fixed-shape pass over the whole incidence structure inside one
    ``lax.while_loop``, so the entire peel is a single jitted call.  For the
    approximate algorithm rounds = O(log^2 n), so this is the TPU-preferred
    backend there (hillclimb lever + measurements in EXPERIMENTS.md).

Both backends record the peel trace (``order_round`` + raw peel values),
which ``interleaved.replay_trace`` consumes to build the ANH-EL hierarchy
without any in-loop callback.  These two entry points back the registered
``dense`` and ``gather`` backends (``repro.core.backends``): the registry
entry declares the capabilities (gather has no compiled loop, so no fused
hierarchy; both record the trace) and ``decompose()`` dispatches through
it — the capability declarations there, not this module, are what
``NucleusConfig.validate()`` derives legality from.
"""
from __future__ import annotations

import dataclasses
from typing import Literal, Optional

import numpy as np
import jax.numpy as jnp

from ..graph import INT
from .engine import BIG, dense_coreness, make_schedule, pallas_by_default
from .incidence import NucleusProblem
from .kcore import kcore_coreness
from .schedule import PeelSchedule


@dataclasses.dataclass
class PeelResult:
    core: jnp.ndarray          # (n_r,) int32 — exact or estimated core numbers
    rounds: int                # number of peel rounds (peeling-complexity proxy)
    order_round: jnp.ndarray   # (n_r,) round index at which each clique peeled
    peel_value: Optional[jnp.ndarray] = None  # (n_r,) raw bucket value
    # assigned at peel time (pre-clipping) — the trace value LINK replay
    # needs; == core for exact peeling.  None is a construction-time
    # sentinel only: __post_init__ replaces it with ``core``, so a
    # materialized PeelResult always carries a real array.
    uf_parent: Optional[jnp.ndarray] = None  # (n_r,) resolved ANH-EL union-
    uf_L: Optional[jnp.ndarray] = None       # find + nearest-lower-core table
    # (hierarchy=True only) — the join forest of the fused LINK fixpoint.

    def __post_init__(self):
        if self.peel_value is None:
            self.peel_value = self.core

    @property
    def has_hierarchy(self) -> bool:
        return self.uf_parent is not None


def _gather_incident_sids(problem: NucleusProblem, a_ids: jnp.ndarray) -> jnp.ndarray:
    """All s-clique ids incident to the peeled set (with duplicates)."""
    off = problem.mem_offsets
    counts = off[a_ids + 1] - off[a_ids]
    total = int(jnp.sum(counts))
    if total == 0:
        return jnp.zeros((0,), INT)
    starts = jnp.cumsum(counts) - counts
    rep = jnp.repeat(jnp.arange(a_ids.shape[0], dtype=INT), counts,
                     total_repeat_length=total)
    pos = jnp.arange(total, dtype=INT) - starts[rep]
    return problem.mem_sids[off[a_ids][rep] + pos]


def _peel_loop(problem: NucleusProblem, schedule: PeelSchedule) -> PeelResult:
    """Work-efficient gather backend: eager host loop, data-dependent shapes.

    The bucket sequence comes from the same ``PeelSchedule`` the compiled
    engine uses (level >= dmin every round, so each iteration peels at least
    the minimum-degree clique and the loop always terminates).
    """
    n_r = problem.n_r
    deg = problem.deg0
    core = jnp.full((n_r,), -1, INT)
    order_round = jnp.full((n_r,), -1, INT)
    peeled = jnp.zeros((n_r,), bool)
    s_alive = jnp.ones((problem.n_s,), bool)
    sched = schedule.init_carry()
    rounds = 0
    n_left = n_r
    while n_left > 0:
        live_deg = jnp.where(peeled, BIG, deg)
        sched, level = schedule.next_level(sched, jnp.min(live_deg))
        a_mask = (~peeled) & (deg <= level)
        core = jnp.where(a_mask, level, core)
        order_round = jnp.where(a_mask, rounds, order_round)
        peeled = peeled | a_mask
        n_left -= int(jnp.sum(a_mask))
        a_ids = jnp.nonzero(a_mask)[0].astype(INT)
        sids = _gather_incident_sids(problem, a_ids)
        if int(sids.shape[0]):
            sids_u = jnp.unique(sids)
            newly = sids_u[s_alive[sids_u]]
            if int(newly.shape[0]):
                s_alive = s_alive.at[newly].set(False)
                members = problem.inc_rid[newly].reshape(-1)
                deg = deg.at[members].add(-1)
        rounds += 1
    return PeelResult(core=core, rounds=rounds, order_round=order_round)


def _run(problem: NucleusProblem, schedule: PeelSchedule,
         backend: Literal["gather", "dense"],
         use_pallas: Optional[bool], hierarchy: bool = False,
         fast_lane: Optional[bool] = None) -> PeelResult:
    if backend == "dense":
        # the r1s2 degenerate case routes to the k-core fast lane (vertex
        # peel + one-shot edge-list fixpoint, ``core.kcore``) unless the
        # caller pins the Pallas round body — the lane the dense backend
        # declares as "kcore" and the planner records in Plan.reasons.
        # fast_lane=True/False forces the routing (tests compare lanes).
        if fast_lane is None:
            wants_pallas = use_pallas or (use_pallas is None
                                          and pallas_by_default())
            fast_lane = (problem.r, problem.s) == (1, 2) \
                and not wants_pallas
        if fast_lane:
            out = kcore_coreness(problem, schedule, hierarchy=hierarchy)
        else:
            out = dense_coreness(problem, schedule, use_pallas=use_pallas,
                                 hierarchy=hierarchy)
        core, order, rounds = out[:3]
        if hierarchy:
            return PeelResult(core=core, rounds=int(rounds),
                              order_round=order, uf_parent=out[4],
                              uf_L=out[5])
        return PeelResult(core=core, rounds=int(rounds), order_round=order)
    res = _peel_loop(problem, schedule)
    if hierarchy:
        # eager backend: the forest comes from the host trace-replay oracle
        # (identical output by the DESIGN.md §4 contract); import is lazy to
        # avoid the peel <-> interleaved cycle
        from .interleaved import replay_trace, _resolve
        state = replay_trace(problem, res)
        parent = _resolve(state.parent, np.arange(problem.n_r,
                                                  dtype=np.int64))
        res = dataclasses.replace(res, uf_parent=jnp.asarray(parent, INT),
                                  uf_L=jnp.asarray(state.L, INT))
    return res


def exact_coreness(problem: NucleusProblem,
                   backend: Literal["gather", "dense"] = "gather",
                   use_pallas: Optional[bool] = None,
                   hierarchy: bool = False,
                   fast_lane: Optional[bool] = None) -> PeelResult:
    """Exact core numbers; hierarchy=True also returns the ANH-EL join
    forest (fused into the same jitted call on the dense backend).
    fast_lane forces the r1s2 k-core lane on/off (None = auto)."""
    return _run(problem, make_schedule(problem, "exact"), backend,
                use_pallas, hierarchy, fast_lane)


def approx_coreness(problem: NucleusProblem, delta: float = 0.1,
                    backend: Literal["gather", "dense"] = "gather",
                    use_pallas: Optional[bool] = None,
                    hierarchy: bool = False,
                    fast_lane: Optional[bool] = None) -> PeelResult:
    """(C(s,r)+eps)-approximate core numbers, eps = (C+delta)(1+delta)/C - C.

    Estimates are >= the true core and <= (C(s,r)+delta)(1+delta) * true core
    (Theorem 6.3).  Practical tightening: assigned value is clipped to the
    clique's original s-clique-degree (paper §6); ``peel_value`` keeps the
    unclipped bucket values because those drove LINK equality during the
    peel (the hierarchy replay must see them — and the fused forest is
    likewise built over the unclipped values).
    """
    res = _run(problem, make_schedule(problem, "approx", delta), backend,
               use_pallas, hierarchy, fast_lane)
    # practical improvement: estimate <= original degree
    core = jnp.minimum(res.core, problem.deg0)
    # still must be >= true core; deg0 >= true core always, so safe.
    return dataclasses.replace(res, core=core, peel_value=res.core)
