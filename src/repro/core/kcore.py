"""The r1s2 (k-core) fast lane: vertex-degree peel, no incidence table.

For (r, s) = (1, 2) the nucleus decomposition degenerates to the classic
k-core: r-cliques are vertices, s-cliques are edges, and the s-clique
degree is just the vertex degree.  The generic engine still pays the full
incidence machinery there — a (m, 2) member gather + any-reduce + scatter
per round, plus (fused hierarchy) a chain-reduction sort over the (m, 2)
rows and a LINK fixpoint invocation EVERY round.  That per-round fixpoint
is what inverted the paper's headline result on r1s2 (EXPERIMENTS.md
hierarchy lane: fused 0.40x vs two-phase before this lane).

This lane exploits two degeneracies:

  * **Peel**: the per-round decrement is a plain adjacency reduction —
    ``delta[v] = #{u in N(v) : u peeled this round}`` over the vertex CSR.
    Each edge {u, v} decrements v exactly once across the whole peel (at
    u's peel round); the generic engine's edge-death bookkeeping reaches
    the same ``deg`` trajectory because decrements against already-peeled
    (frozen) vertices are no-ops in both formulations, so core/order/
    rounds are bit-identical to the generic engine (tests pin this).
  * **Hierarchy**: with C = 2 the chain reduction degenerates — every
    edge emits EXACTLY ONE link {u, v} over the whole peel (the chain link
    when both endpoints peel together, the head-to-representative link
    otherwise).  The total link multiset is therefore the edge list
    itself, and since ``engine.link_fixpoint`` is confluent (the result
    depends only on the link multiset, not on arrival order — DESIGN.md
    §5), ONE post-peel fixpoint over the edge list with the final raw
    core values replaces rounds-many in-loop invocations.  This is the
    whole speedup: O(rounds · fixpoint) becomes O(1 · fixpoint).

The lane reuses ``run_peel_engine`` via its ``fused_round`` hook (same
schedule, same trace semantics, same while_loop) and is declared as the
``"kcore"`` fast lane on the dense backend's capabilities so the planner
records the routing in ``Plan.reasons``.  ``peel._run`` routes
(r, s) = (1, 2) dense peels here unless the caller pins the Pallas
path (``use_pallas=True`` keeps the generic engine so the Pallas round
body stays exercised on r1s2 fixtures too).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..graph import INT
from .engine import (FIXPOINT_COUNTS, h_index_rows, link_fixpoint,
                     run_peel_engine)
from .incidence import NucleusProblem
from .schedule import PeelSchedule


@jax.jit
def kcore_local_converge(nbr_idx, vals0, frozen, max_sweeps):
    """Restartable-from-state local k-core iteration (the r1s2 degeneracy
    of ``engine.local_converge``): with C = 2 the per-s-clique "min of the
    other members" is just the neighbor's value, so one Jacobi sweep is a
    direct adjacency gather + h-index — no incidence-slot indirection.

    nbr_idx: (m, d) neighbor indices into the subproblem's vertex space
    (sentinel ``m`` reads -1, which the h-index ignores); vals0/frozen/
    max_sweeps as in ``engine.local_converge``.  Shapes key the jit cache:
    the streaming path pads to pow2 buckets so updates stay warm.
    Returns (vals, sweeps).
    """
    m = vals0.shape[0]

    def cond(st):
        _, done, sweeps = st
        return (~done) & (sweeps < max_sweeps)

    def body(st):
        vals, _, sweeps = st
        flat = jnp.concatenate([vals, jnp.full((1,), -1, INT)])
        theta = h_index_rows(flat[jnp.clip(nbr_idx, 0, m)])
        new = jnp.where(frozen, vals, jnp.minimum(vals, theta))
        return new, jnp.all(new == vals), sweeps + 1

    vals, _, sweeps = jax.lax.while_loop(
        cond, body, (vals0, jnp.zeros((), bool), jnp.zeros((), INT)))
    return vals, sweeps


def kcore_plan(problem: NucleusProblem):
    """Vertex-adjacency CSR slots: (vids, nbrs), both (2m,), vids sorted.

    Slot k says: vertex ``vids[k]`` has neighbor ``nbrs[k]``.  Built once
    per problem (memoized on it) — the per-round decrement is then
    ``segment_add(a_mask[nbrs] by vids)``.
    """
    cached = getattr(problem, "_kcore_plan", None)
    if cached is not None:
        return cached
    e = np.asarray(problem.g.edges)
    src = np.concatenate([e[:, 0], e[:, 1]])
    dst = np.concatenate([e[:, 1], e[:, 0]])
    order = np.argsort(src, kind="stable")
    plan = (jnp.asarray(src[order], INT), jnp.asarray(dst[order], INT))
    problem._kcore_plan = plan
    return plan


@partial(jax.jit, static_argnames=("schedule", "max_rounds", "hierarchy",
                                   "link_width"))
def _kcore_engine(vids, nbrs, edges, deg0, *, schedule: PeelSchedule,
                  max_rounds: int, hierarchy: bool,
                  link_width: Optional[int] = None):
    n = deg0.shape[0]
    def fused_round(deg, peeled, core, order, level, rnd):
        a = (~peeled) & (deg <= level)
        newp = peeled | a
        core = jnp.where(a, level, core)
        order = jnp.where(a, rnd, order)
        # delta[v] = # newly peeled neighbors; decrements against frozen
        # (already peeled) vertices are masked below, matching the generic
        # engine's edge-death accounting exactly
        delta = jnp.zeros((n,), INT).at[vids].add(a[nbrs].astype(INT))
        deg = jnp.where(newp, deg, deg - delta)
        return deg, newp, core, order

    dummy_inc = jnp.zeros((0, 2), INT)
    out = run_peel_engine(dummy_inc, deg0, schedule, max_rounds=max_rounds,
                          fused_round=fused_round)
    if not hierarchy:
        return out
    core, order, rounds = out[:3]
    # ONE fixpoint over the whole edge-list link multiset (see module
    # docstring): same (parent, L) as the per-round fused engine by the
    # confluence of link_fixpoint, at a single invocation's cost.
    parent0 = jnp.arange(n, dtype=INT)
    L0 = jnp.full((n,), -1, INT)
    lvalid = jnp.ones((edges.shape[0],), bool)
    parent, L, counts = link_fixpoint(parent0, L0, core, edges[:, 0],
                                      edges[:, 1], lvalid, _width=link_width)
    return core, order, rounds, counts, parent, L


def kcore_coreness(problem: NucleusProblem, schedule: PeelSchedule, *,
                   max_rounds: Optional[int] = None,
                   hierarchy: bool = False):
    """Drop-in for ``dense_coreness`` on an (r, s) = (1, 2) problem.

    Same return contract: (core_raw, order_round, rounds, counts[, parent,
    L]), the core, order and forest bit-identical to the generic dense
    engine (and, for the hierarchy, to the host replay oracle) — the
    golden tests pin both.  The counts are this lane's one fixpoint's,
    over the whole edge list (n slots wide, so about m / n fills).
    """
    assert (problem.r, problem.s) == (1, 2), \
        f"kcore lane needs (r, s) = (1, 2), got {(problem.r, problem.s)}"
    n = problem.n_r
    if max_rounds is None:
        max_rounds = n + 2
    if n == 0:
        empty = jnp.zeros((0,), INT)
        out = (empty, empty, jnp.zeros((), INT),
               jnp.zeros((len(FIXPOINT_COUNTS),), INT))
        return out + (empty, empty) if hierarchy else out
    vids, nbrs = kcore_plan(problem)
    edges = jnp.asarray(problem.g.edges, INT).reshape(-1, 2)
    return _kcore_engine(vids, nbrs, edges, problem.deg0,
                         schedule=schedule, max_rounds=max_rounds,
                         hierarchy=hierarchy)
