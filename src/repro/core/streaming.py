"""Incremental nucleus maintenance: ``Decomposition.update(GraphDelta)``.

The serving lane (DESIGN.md §8/§9) froze the artifact: any edge change
forced a full rebuild + re-peel, and — because the problem shapes change
with every edge — a fresh XLA compile on top.  This module maintains the
decomposition under edge inserts/deletes by *local* work (DESIGN.md §10):

  1. **Problem surgery.**  The canonical tables are edited directly: for
     (2, 3) the r-clique table IS the lexsorted edge list, so an edge
     toggle is one ``searchsorted`` row insert/delete plus a vectorized
     rid remap of the incidence rows; new triangles come from the common
     neighborhood of the toggled edge, dead ones straight off the edge's
     mem-CSR row.  No clique re-enumeration, no orientation, no expansion.
  2. **Affected region.**  Only r-cliques connected to the touched
     s-cliques through a path of s-cliques whose old-core bottleneck
     reaches their own old core can change (insert: the single-edge rise
     bound caps the change at +1; delete: old values are upper bounds).
     The region comes from a vectorized max-min label propagation seeded
     at the touched s-cliques.
  3. **Local convergence.**  Values converge downward from an upper-bound
     seed by the h-operator Jacobi sweep (``engine.local_converge``; the
     r1s2 degeneracy rides ``kcore.kcore_local_converge`` — the PR-6 fast
     lane's adjacency layout), run over the extracted subproblem padded
     to pow2 shape buckets, so a stream of updates reuses ONE compiled
     executable per shape class instead of cold-compiling per edge.
  4. **Forest patch.**  The join forest is a pure function of (core
     values, link multiset) — ``link_fixpoint`` is confluent over
     peel-order link streams (DESIGN.md §5) — so an insert that creates
     no s-clique and moves no value is a pure rid relabeling of the
     resolved forest, and every other op re-presents the canonical chain
     multiset (members of each s-clique sorted by core, consecutive
     pairs linked) in ONE fixpoint call: linear work, no peel rounds,
     same padded warm buckets.  (Continuing the fixpoint from the
     resolved state with only the new chains is tempting but unsound:
     L ties break by arrival history, and a late low-core link can merge
     components whose subsumed L candidates are never re-presented.)

``decompose()`` stays the parity oracle: tests pin every update
array-for-array (core, peel values, forest, tree, cuts) against a fresh
decompose of the edited graph under randomized insert/delete sequences.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..graph import INT
from ..graph.container import Graph
from .engine import BIG, link_fixpoint, local_converge
from .incidence import NucleusProblem
from .kcore import kcore_local_converge

# (r, s) pairs with a problem-surgery implementation.  The local theory
# (region bound + h-operator) is generic; what is specialized here is the
# incremental table edit: the r-clique table must be a cheap function of
# the edge list (r=1: the vertices; r=2: the edge list itself).
SUPPORTED_RS = ((1, 2), (2, 3))

# pow2 pad floors for the compiled local stages — small enough that tiny
# fixtures stay tiny, large enough that a real stream collapses onto a
# handful of shape classes (same rationale as session.DEFAULT_BUCKET_FLOOR)
SUB_FLOOR = 64
DEG_FLOOR = 8

Hook = Optional[Callable[[Tuple], None]]


def _pow2(n: int, floor: int) -> int:
    n = max(int(n), int(floor), 1)
    return 1 << (n - 1).bit_length()


# ---------------------------------------------------------------------------
# The delta type
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GraphDelta:
    """An edge-set change: ``delete`` rows are removed first, then
    ``insert`` rows are added, each applied ONE EDGE AT A TIME (the
    single-edge rise/fall bounds that seed the affected region are
    per-edge facts; batching would need the weaker multi-edge bounds).

    Rows are (u, v) vertex pairs in either order; self-loops are
    rejected, as are inserts of present edges / deletes of absent ones
    (strict by design — a no-op delta usually means the caller's view of
    the graph has drifted).  The vertex set is fixed: deltas change
    edges, not ``n``.
    """

    insert: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0, 2), np.int64))
    delete: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0, 2), np.int64))

    def __post_init__(self):
        for name in ("insert", "delete"):
            e = np.asarray(getattr(self, name), np.int64).reshape(-1, 2)
            if e.size and (e[:, 0] == e[:, 1]).any():
                raise ValueError(f"GraphDelta.{name} contains a self-loop")
            lo = np.minimum(e[:, 0], e[:, 1])
            hi = np.maximum(e[:, 0], e[:, 1])
            object.__setattr__(self, name, np.stack([lo, hi], axis=1))

    @property
    def n_ops(self) -> int:
        return int(self.insert.shape[0]) + int(self.delete.shape[0])

    def ops(self) -> Iterator[Tuple[str, int, int]]:
        for u, v in self.delete:
            yield ("delete", int(u), int(v))
        for u, v in self.insert:
            yield ("insert", int(u), int(v))


# ---------------------------------------------------------------------------
# Canonical table surgery
# ---------------------------------------------------------------------------

def _edge_keys(edges: np.ndarray) -> np.ndarray:
    e = np.asarray(edges, np.int64)
    return (e[:, 0] << 32) | e[:, 1]


def _apply_edge(g: Graph, u: int, v: int, op: str) -> Tuple[Graph, int]:
    """Toggle one canonical edge; returns (new graph, touched row)."""
    if not (0 <= u < v < g.n):
        raise ValueError(f"edge ({u}, {v}) out of range for n={g.n}")
    e = np.asarray(g.edges, np.int64).reshape(-1, 2)
    keys = _edge_keys(e)
    pos = int(np.searchsorted(keys, (u << 32) | v))
    present = pos < keys.shape[0] and keys[pos] == ((u << 32) | v)
    if op == "insert":
        if present:
            raise ValueError(f"insert of present edge ({u}, {v})")
        new = np.insert(e, pos, (u, v), axis=0)
    else:
        if not present:
            raise ValueError(f"delete of absent edge ({u}, {v})")
        new = np.delete(e, pos, axis=0)
    return Graph(n=g.n, edges=jnp.asarray(new, INT)), pos


def _mem_csr(inc: np.ndarray, n_r: int):
    """(mem_offsets, mem_sids, deg0) from 2D incidence rows — the same
    stable (rid, then sid-ascending) grouping the builders produce."""
    flat = inc.reshape(-1)
    deg0 = np.bincount(flat, minlength=n_r).astype(np.int32) if flat.size \
        else np.zeros((n_r,), np.int32)
    off = np.zeros((n_r + 1,), np.int64)
    np.cumsum(deg0, out=off[1:])
    order = np.argsort(flat, kind="stable")
    sids = (order // max(inc.shape[1], 1)).astype(np.int32)
    return off, sids, deg0


def _pack_problem(old: NucleusProblem, g: Graph, r_table: np.ndarray,
                  inc: np.ndarray) -> NucleusProblem:
    n_r = int(r_table.shape[0])
    off, sids, deg0 = _mem_csr(inc, n_r)
    return NucleusProblem(
        g=g, r=old.r, s=old.s,
        r_cliques=jnp.asarray(r_table, INT).reshape(n_r, old.r),
        inc_rid=jnp.asarray(inc, INT).reshape(inc.shape[0], old.n_sub),
        mem_offsets=jnp.asarray(off, INT), mem_sids=jnp.asarray(sids, INT),
        deg0=jnp.asarray(deg0, INT), orientation=old.orientation,
        build_stats={"build": "streaming"})


@dataclasses.dataclass
class _OpEdit:
    """Everything one edge toggle did to the problem tables."""

    problem: NucleusProblem
    rid_map: Optional[np.ndarray]   # old rid -> new rid; None = identity
    new_rids: np.ndarray            # new-space ids of created r-cliques
    new_sids: np.ndarray            # new-space ids of created s-cliques
    seed_best: np.ndarray           # (n_r_new,) initial bottleneck labels


def _edit_12(problem: NucleusProblem, g_new: Graph, u: int, v: int,
             op: str, core_old: np.ndarray) -> _OpEdit:
    """(1, 2): r-cliques are the vertices (rid space fixed), s-cliques
    the edges — one incidence row toggles.  The builder's s-row order is
    DAG-expansion order, NOT the lexsorted edge order, so rows are
    located by content; new rows append (s-order is free: every output
    is rid-indexed and the forest is confluent over the link multiset).
    """
    inc_old = np.asarray(problem.inc_rid, np.int64).reshape(-1, 2)
    seed_best = np.full((problem.n_r,), -1, np.int64)
    if op == "insert":
        inc = np.concatenate([inc_old, np.array([[u, v]], np.int64)])
        new_sids = np.array([inc.shape[0] - 1], np.int64)
    else:
        row = int(np.flatnonzero((inc_old[:, 0] == u)
                                 & (inc_old[:, 1] == v))[0])
        # seeds: the dead edge's surviving endpoints, at the dead
        # s-clique's bottleneck under the OLD core values
        seed_best[inc_old[row]] = core_old[inc_old[row]].min()
        inc = np.delete(inc_old, row, axis=0)
        new_sids = np.zeros((0,), np.int64)
    new = _pack_problem(problem, g_new,
                        np.asarray(problem.r_cliques, np.int64), inc)
    return _OpEdit(problem=new, rid_map=None,
                   new_rids=np.zeros((0,), np.int64), new_sids=new_sids,
                   seed_best=seed_best)


def _neighbors(e: np.ndarray, x: int) -> np.ndarray:
    return np.concatenate([e[e[:, 0] == x, 1], e[e[:, 1] == x, 0]])


def _edit_23(problem: NucleusProblem, g_new: Graph, pos: int, op: str,
             u: int, v: int, core_old: np.ndarray) -> _OpEdit:
    """(2, 3): the r-clique table IS the lexsorted edge list — one row
    shifts the rid space by one; triangles toggle with the edge."""
    inc_old = np.asarray(problem.inc_rid, np.int64).reshape(-1, 3)
    n_r_old = problem.n_r
    e_new = np.asarray(g_new.edges, np.int64).reshape(-1, 2)
    if op == "insert":
        rid_map = np.arange(n_r_old, dtype=np.int64)
        rid_map[pos:] += 1
        inc = rid_map[inc_old]
        # every new triangle contains the new edge: enumerate the common
        # neighborhood of its endpoints in the NEW graph
        ws = np.intersect1d(_neighbors(e_new, u), _neighbors(e_new, v))
        if ws.size:
            tris = np.sort(np.stack(
                [np.full(ws.shape, u), np.full(ws.shape, v), ws],
                axis=1), axis=1)
            pairs = np.stack([tris[:, [0, 1]], tris[:, [0, 2]],
                              tris[:, [1, 2]]], axis=1)      # (t, 3, 2)
            rids = np.searchsorted(_edge_keys(e_new), _edge_keys(
                pairs.reshape(-1, 2))).reshape(-1, 3)
            inc = np.concatenate([inc, rids], axis=0)
            new_sids = np.arange(inc.shape[0] - rids.shape[0],
                                 inc.shape[0], dtype=np.int64)
        else:
            new_sids = np.zeros((0,), np.int64)
        new_rids = np.array([pos], np.int64)
        # the fresh rid is unconditionally a candidate; its (new)
        # incident s-cliques seed their other members via the generic
        # new-sid fold in _apply_op
        seed_best = np.full((n_r_old + 1,), -1, np.int64)
        seed_best[pos] = BIG
    else:
        off = np.asarray(problem.mem_offsets, np.int64)
        msids = np.asarray(problem.mem_sids, np.int64)
        dead = msids[off[pos]:off[pos + 1]]
        rid_map = np.arange(n_r_old, dtype=np.int64)
        rid_map[pos] = -1
        rid_map[pos + 1:] -= 1
        seed_best = np.full((n_r_old - 1,), -1, np.int64)
        if dead.size:
            dead_rows = inc_old[dead]                    # old rid space
            # bottleneck of a dead triangle = min OLD core over ALL its
            # members (the deleted edge included: the triangle only
            # supported a member at level c if every member sat at >= c)
            w = core_old[dead_rows].min(axis=1)          # (t,)
            live = rid_map[dead_rows]                    # (t, 3); -1 = e0
            np.maximum.at(seed_best, np.clip(live, 0, None).reshape(-1),
                          np.where(live >= 0, w[:, None], -1).reshape(-1))
        keep = np.ones((inc_old.shape[0],), bool)
        keep[dead] = False
        inc = rid_map[inc_old[keep]]
        new_rids = np.zeros((0,), np.int64)
        new_sids = np.zeros((0,), np.int64)
    new = _pack_problem(problem, g_new, e_new, inc)
    return _OpEdit(problem=new, rid_map=rid_map, new_rids=new_rids,
                   new_sids=new_sids, seed_best=seed_best)


# ---------------------------------------------------------------------------
# Affected region: vectorized max-min (bottleneck) label propagation
# ---------------------------------------------------------------------------

def _region(inc: np.ndarray, off: np.ndarray, msids: np.ndarray,
            core_u: np.ndarray, best0: np.ndarray) -> np.ndarray:
    """Largest bottleneck label reachable from the seeds, per r-clique.

    A label b entering s-clique S leaves as min(b, min over S's members
    of ``core_u``); candidates for change are exactly the r-cliques whose
    final label reaches their own ``core_u`` (the witness-subgraph /
    cascade arguments of DESIGN.md §10).  Labels only grow, each step is
    a vectorized scatter-max over the frontier's incidence — a max-min
    Bellman–Ford that settles in at most #distinct-label rounds.
    """
    best = best0.copy()
    if not inc.size:
        return best
    swt = core_u[inc].min(axis=1)          # (n_s,) s-clique bottleneck
    frontier = np.flatnonzero(best >= 0)
    while frontier.size:
        cnt = (off[frontier + 1] - off[frontier]).astype(np.int64)
        total = int(cnt.sum())
        if total == 0:
            break
        starts = np.cumsum(cnt) - cnt
        idx = np.arange(total, dtype=np.int64) \
            - np.repeat(starts, cnt) + np.repeat(off[frontier], cnt)
        sids = msids[idx]
        w = np.minimum(np.repeat(best[frontier], cnt), swt[sids])
        mem = inc[sids]                                  # (k, C)
        new = best.copy()
        np.maximum.at(new, mem.reshape(-1),
                      np.broadcast_to(w[:, None], mem.shape).reshape(-1))
        frontier = np.flatnonzero(new > best)
        best = new
    return best


def _prune_rise(inc: np.ndarray, core_u: np.ndarray, cand: np.ndarray,
                f0: np.ndarray, protect: np.ndarray):
    """Shrink the candidate set before the compiled converge — INSERT
    ops only.

    A single insert only ever RAISES cores, and ``f0`` is a valid upper
    bound on every final value; theta is monotone in its inputs, so a
    candidate R whose support count even under these upper bounds cannot
    reach ``core_u[R] + 1`` (fewer than k+1 incident s-cliques whose
    other members all bound >= k+1) provably keeps its old core.
    Freezing it lowers the bound its neighbors see — iterate the
    (monotone) screen to a fixpoint.  Pure screening: any candidate it
    cannot disprove goes to the compiled converge unchanged, so parity
    is untouched.  Without it, uniform-core graphs (a BA 8-core) flood
    the region bound and the "local" converge is the whole graph.

    ``protect`` marks rids that must stay candidates regardless (fresh
    rids whose ``core_u`` is the BIG sentinel, not a real old value).
    """
    if not cand.any() or not inc.size:
        return cand, f0
    if inc.shape[1] == 2:
        return _prune_rise_pairs(inc, core_u, cand, f0, protect)
    cand = cand.copy()
    f0 = f0.copy()
    n_r = core_u.shape[0]
    thr = core_u + 1                       # the level a riser must reach
    # Only rows touching a live candidate can change a verdict, and the
    # set shrinks monotonically as rids freeze — subset per sweep so the
    # cascade tail costs |frontier|, not |incidence|.
    live = np.flatnonzero(cand[inc].any(axis=1))
    for _ in range(64):
        sub = inc[live]
        row_vals = f0[sub]                               # (rows, C)
        part = np.partition(row_vals, 1, axis=1)         # C >= 2 (r < s)
        m1, m2 = part[:, 0], part[:, 1]
        is_min = row_vals == m1[:, None]
        unique_min = is_min.sum(axis=1) == 1
        # min over the OTHER members, per member slot
        others = np.where(is_min & unique_min[:, None],
                          m2[:, None], m1[:, None])
        support = others >= thr[sub]
        cnt = np.zeros((n_r,), np.int64)
        np.add.at(cnt, sub[support], 1)
        newly = cand & ~protect & (cnt < thr)
        if not newly.any():
            break
        cand[newly] = False
        f0[newly] = core_u[newly]
        live = live[cand[sub].any(axis=1)]
    return cand, f0


def _delete_keeps_cores(core_u: np.ndarray, perturbed: np.ndarray,
                        inc: np.ndarray, off: np.ndarray,
                        msids: np.ndarray) -> bool:
    """Exact early-out for DELETE ops: do the old cores survive as-is?

    Deletion only ever lowers values, and the cores are the greatest
    assignment c with c <= theta(c).  The old assignment stays feasible
    in the edited problem unless some rid lost support — and only
    members of the removed s-cliques changed incidence at all.  So if
    every perturbed rid still counts >= c(x) incident s-cliques whose
    other members all sit at >= c(x) (under the OLD values), the old
    assignment is still a fixpoint, hence still greatest: nothing moves
    and the compiled converge can be skipped entirely.
    """
    for x in perturbed:
        k = int(core_u[x])
        if k <= 0:
            continue
        sids = msids[off[x]:off[x + 1]]
        if sids.size < k:
            return False
        rows = inc[sids]                                 # (d, C)
        others = np.where(rows == x, BIG, core_u[rows]).min(axis=1)
        if int((others >= k).sum()) < k:
            return False
    return True


def _prune_rise_pairs(inc: np.ndarray, core_u: np.ndarray, cand: np.ndarray,
                      f0: np.ndarray, protect: np.ndarray):
    """The C == 2 (r1s2) case of the rise screen as a worklist.

    Same fixpoint as the sweep loop above, but freezes propagate through
    an incidence CSR so a row is only revisited when one of its members
    actually drops — O(m) amortized instead of O(m * cascade depth),
    which is what a uniform-core flood (the whole graph as candidates)
    would otherwise cost.  Support only ever flips True -> False (f0 is
    nonincreasing, thr fixed), so decrement-on-flip is exact.
    """
    cand = cand.copy()
    f0 = f0.copy()
    n_r = core_u.shape[0]
    thr = core_u + 1
    a = inc[:, 0].astype(np.int64)
    b = inc[:, 1].astype(np.int64)
    sup_a = f0[b] >= thr[a]                # row's support for member a
    sup_b = f0[a] >= thr[b]
    cnt = np.zeros((n_r,), np.int64)
    np.add.at(cnt, a[sup_a], 1)
    np.add.at(cnt, b[sup_b], 1)
    # rows incident to each rid, CSR over both endpoint columns
    ends = np.concatenate([a, b])
    row_of = np.concatenate([np.arange(a.size), np.arange(b.size)])
    order = np.argsort(ends, kind="stable")
    rows_s = row_of[order]
    starts = np.searchsorted(ends[order], np.arange(n_r + 1))
    kill = np.flatnonzero(cand & ~protect & (cnt < thr))
    while kill.size:
        cand[kill] = False
        f0[kill] = core_u[kill]
        deg = starts[kill + 1] - starts[kill]
        idx = np.repeat(starts[kill], deg) \
            + np.arange(int(deg.sum())) - np.repeat(np.cumsum(deg) - deg, deg)
        tr = np.unique(rows_s[idx])
        new_sa = f0[b[tr]] >= thr[a[tr]]
        new_sb = f0[a[tr]] >= thr[b[tr]]
        drop_a = a[tr][sup_a[tr] & ~new_sa]
        drop_b = b[tr][sup_b[tr] & ~new_sb]
        np.subtract.at(cnt, drop_a, 1)
        np.subtract.at(cnt, drop_b, 1)
        sup_a[tr] = new_sa
        sup_b[tr] = new_sb
        hit = np.unique(np.concatenate([drop_a, drop_b]))
        hit = hit[cand[hit] & ~protect[hit]]
        kill = hit[cnt[hit] < thr[hit]]
    return cand, f0


# ---------------------------------------------------------------------------
# Local convergence over the extracted subproblem (padded, compiled)
# ---------------------------------------------------------------------------

def _csr_fill(keys: np.ndarray, vals: np.ndarray, rows: int, d_pad: int,
              sentinel: int) -> np.ndarray:
    """Grouped fill: row ``keys[k]`` gets ``vals[k]`` in its next free
    column (stable in k); unused cells hold ``sentinel``."""
    out = np.full((rows, d_pad), sentinel, np.int64)
    if keys.size:
        order = np.argsort(keys, kind="stable")
        degs = np.bincount(keys, minlength=rows)
        starts = np.cumsum(degs) - degs
        occ = np.arange(keys.size, dtype=np.int64) \
            - np.repeat(starts, degs)
        out[keys[order], occ] = vals[order]
    return out


def _converge(problem: NucleusProblem, f0: np.ndarray, cand: np.ndarray,
              hook: Hook) -> Tuple[np.ndarray, int]:
    """Run the padded compiled local iteration; returns (values, sweeps).

    ``f0`` must dominate the true new core values pointwise on the
    candidate set and carry the exact values elsewhere (frozen ring).
    """
    n_r = f0.shape[0]
    cand_idx = np.flatnonzero(cand)
    if cand_idx.size == 0:
        return f0, 0
    inc = np.asarray(problem.inc_rid, np.int64).reshape(problem.n_s,
                                                        problem.n_sub)
    off = np.asarray(problem.mem_offsets, np.int64)
    msids = np.asarray(problem.mem_sids, np.int64)
    cnt = (off[cand_idx + 1] - off[cand_idx]).astype(np.int64)
    total = int(cnt.sum())
    if total == 0:
        # isolated candidates: the h-operator over no s-cliques is 0
        out = f0.copy()
        out[cand_idx] = 0
        return out, 0
    starts = np.cumsum(cnt) - cnt
    idx = np.arange(total, dtype=np.int64) \
        - np.repeat(starts, cnt) + np.repeat(off[cand_idx], cnt)
    sids = np.unique(msids[idx])
    sub_r = np.unique(np.concatenate([cand_idx, inc[sids].reshape(-1)]))
    inv = np.full((n_r,), -1, np.int64)
    inv[sub_r] = np.arange(sub_r.size)
    inc_sub = inv[inc[sids]]                         # (k, C), all >= 0
    k, C = inc_sub.shape
    m_pad = _pow2(sub_r.size, SUB_FLOOR)
    vals = np.zeros((m_pad,), np.int32)
    vals[:sub_r.size] = f0[sub_r]
    frozen = np.ones((m_pad,), bool)
    frozen[:sub_r.size] = ~cand[sub_r]
    # every sweep but the last strictly lowers some candidate and values
    # are bounded below by 0 — the seed sum caps the loop
    cap = int(vals[:sub_r.size][~frozen[:sub_r.size]].sum()) + 2
    if (problem.r, problem.s) == (1, 2):
        # k-core fast lane: C = 2 rows ARE edges — direct adjacency
        src = np.concatenate([inc_sub[:, 0], inc_sub[:, 1]])
        dst = np.concatenate([inc_sub[:, 1], inc_sub[:, 0]])
        d_pad = _pow2(int(np.bincount(src, minlength=1).max()), DEG_FLOOR)
        nbr = _csr_fill(src, dst, m_pad, d_pad, sentinel=m_pad)
        if hook is not None:
            hook(("stream-converge", 1, 2, m_pad, d_pad))
        out, sweeps = kcore_local_converge(
            jnp.asarray(nbr, INT), jnp.asarray(vals),
            jnp.asarray(frozen), jnp.asarray(cap, INT))
    else:
        rows_pad = _pow2(k, SUB_FLOOR)
        inc_pad = np.full((rows_pad, C), -1, np.int32)
        inc_pad[:k] = inc_sub
        # flat slot index row * C + col is invariant under row padding
        # (rows append at the end), so the gather table stays valid
        flat = inc_sub.reshape(-1)
        slots = np.arange(flat.size, dtype=np.int64)
        d_pad = _pow2(int(np.bincount(flat, minlength=1).max()), DEG_FLOOR)
        gather = _csr_fill(flat, slots, m_pad, d_pad,
                           sentinel=rows_pad * C)
        if hook is not None:
            hook(("stream-converge", problem.r, problem.s, rows_pad,
                  m_pad, d_pad))
        out, sweeps = local_converge(
            jnp.asarray(inc_pad, INT), jnp.asarray(gather, INT),
            jnp.asarray(vals), jnp.asarray(frozen),
            jnp.asarray(cap, INT))
    f = f0.copy()
    sel = ~frozen[:sub_r.size]
    f[sub_r[sel]] = np.asarray(out)[:sub_r.size][sel]
    return f, int(sweeps)


# ---------------------------------------------------------------------------
# Forest patch: confluent link fixpoint over canonical chains
# ---------------------------------------------------------------------------

def _chains(inc: np.ndarray, core: np.ndarray):
    """Canonical per-s-clique chains: members sorted by core (ascending,
    stable), consecutive pairs linked.  The chain multiset over ALL
    s-cliques with the final core values resolves to exactly the fused
    engine's (parent, L) — confluence of ``link_fixpoint`` (DESIGN.md
    §5/§10; the golden parity tests pin it)."""
    if not inc.size:
        z = np.zeros((0,), np.int64)
        return z, z
    order = np.argsort(core[inc], axis=1, kind="stable")
    mem = np.take_along_axis(inc, order, axis=1)
    return mem[:, :-1].reshape(-1), mem[:, 1:].reshape(-1)


@partial(jax.jit, static_argnames="link_width")
def _fixpoint_padded(parent0, L0, core, la, lb, lv, *,
                     link_width: Optional[int] = None):
    return link_fixpoint(parent0, L0, core, la, lb, lv,
                         _width=link_width)[:2]


def _run_fixpoint(parent0: np.ndarray, L0: np.ndarray, core: np.ndarray,
                  la: np.ndarray, lb: np.ndarray,
                  hook: Hook) -> Tuple[np.ndarray, np.ndarray]:
    n_r = parent0.shape[0]
    if la.size == 0:
        return parent0, L0
    # pad to pow2 buckets: padded rids are isolated self-roots with core
    # -1 and no links, so they never interact with the real slots
    n_pad = _pow2(n_r, SUB_FLOOR)
    k_pad = _pow2(la.size, SUB_FLOOR)
    pp = np.concatenate([parent0, np.arange(n_r, n_pad, dtype=np.int64)])
    Lp = np.concatenate([L0, np.full((n_pad - n_r,), -1, np.int64)])
    cp = np.concatenate([core, np.full((n_pad - n_r,), -1, np.int64)])
    lap = np.zeros((k_pad,), np.int64)
    lbp = np.zeros((k_pad,), np.int64)
    lvp = np.zeros((k_pad,), bool)
    lap[:la.size], lbp[:la.size], lvp[:la.size] = la, lb, True
    if hook is not None:
        hook(("stream-link", n_pad, k_pad))
    p, L = _fixpoint_padded(jnp.asarray(pp, INT), jnp.asarray(Lp, INT),
                            jnp.asarray(cp, INT), jnp.asarray(lap, INT),
                            jnp.asarray(lbp, INT), jnp.asarray(lvp))
    return (np.asarray(p, np.int64)[:n_r], np.asarray(L, np.int64)[:n_r])


# ---------------------------------------------------------------------------
# The per-op driver + public entry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class UpdateStats:
    """Telemetry of one ``update()`` call (summed over its ops)."""

    ops: int = 0
    candidates: int = 0           # r-cliques seeded as possible changers
    changed: int = 0              # r-cliques whose core actually moved
    sweeps: int = 0               # compiled Jacobi sweeps run
    incremental_relinks: int = 0  # forest kept: pure rid relabeling
    full_relinks: int = 0         # forest re-resolved from full multiset


def _remap_forest(parent: np.ndarray, L: np.ndarray,
                  edit: _OpEdit) -> Tuple[np.ndarray, np.ndarray]:
    """Carry the resolved forest into the new rid space (insert only:
    positions shift by one; the fresh rid starts as its own root)."""
    if edit.rid_map is None:
        return parent, L
    p = edit.rid_map[parent]
    Lr = np.where(L >= 0, edit.rid_map[np.clip(L, 0, None)], -1)
    for rid in edit.new_rids:
        p = np.insert(p, rid, rid)
        Lr = np.insert(Lr, rid, -1)
    return p, Lr


def _apply_op(problem: NucleusProblem, core: np.ndarray,
              parent: Optional[np.ndarray], L: Optional[np.ndarray],
              op: str, u: int, v: int, stats: UpdateStats, hook: Hook):
    g_new, pos = _apply_edge(problem.g, u, v, op)
    rs = (problem.r, problem.s)
    core_old = core.astype(np.int64)
    if rs == (1, 2):
        edit = _edit_12(problem, g_new, u, v, op, core_old)
        core_u = core_old                         # rid space unchanged
    else:
        edit = _edit_23(problem, g_new, pos, op, u, v, core_old)
        # old values carried into the NEW rid space; BIG marks the fresh
        # rid so min(core_u + 1, deg0) seeds it at its degree bound
        core_u = (np.insert(core_old, pos, BIG) if op == "insert"
                  else np.delete(core_old, pos))
    new_p = edit.problem
    n_r = new_p.n_r
    deg0 = np.asarray(new_p.deg0, np.int64)
    inc = np.asarray(new_p.inc_rid, np.int64).reshape(new_p.n_s,
                                                      new_p.n_sub)
    off = np.asarray(new_p.mem_offsets, np.int64)
    msids = np.asarray(new_p.mem_sids, np.int64)
    # fold inserted s-cliques into the seeds: each new s-clique S pushes
    # its bottleneck w(S) (under the carried upper labels) to its members
    best0 = edit.seed_best
    is_new = np.zeros((n_r,), bool)
    is_new[edit.new_rids] = True
    if op == "delete" and _delete_keeps_cores(
            core_u, np.flatnonzero(best0 >= 0), inc, off, msids):
        # feasibility held at every perturbed rid — skip region/converge
        f = core_u.astype(np.int64)
    else:
        if edit.new_sids.size:
            new_rows = inc[edit.new_sids]
            swt = core_u[new_rows].min(axis=1)
            np.maximum.at(best0, new_rows.reshape(-1),
                          np.broadcast_to(swt[:, None],
                                          new_rows.shape).reshape(-1))
        best = _region(inc, off, msids, core_u, best0)
        cand = (best >= 0) & (best >= core_u)
        bump = 1 if op == "insert" else 0
        f0 = np.where(cand, np.minimum(core_u + bump, deg0), core_u)
        if op == "insert":
            cand, f0 = _prune_rise(inc, core_u, cand, f0, is_new)
        # counted AFTER the rise screen: what the compiled converge pays
        stats.candidates += int(cand.sum())
        if cand.any():
            f, sweeps = _converge(new_p, f0.astype(np.int64), cand, hook)
            stats.sweeps += sweeps
        else:
            # the screen disproved every rise: f0 has already been frozen
            # back to core_u everywhere, so skip the compiled dispatch
            f = f0.astype(np.int64)
    changed_existing = (f != core_u) & ~is_new
    stats.changed += int(changed_existing.sum()) + int(is_new.sum())
    core_new = f.astype(np.int64)
    if parent is None:
        return new_p, core_new, None, None
    if op == "insert" and not changed_existing.any() \
            and edit.new_sids.size == 0:
        # insert that creates no s-clique and moves no value: the link
        # multiset and cores are untouched, so the resolved forest just
        # relabels into the new rid space (the fresh rid, in no link, is
        # its own root) — no fixpoint call at all
        parent_new, L_new = _remap_forest(parent, L, edit)
        stats.incremental_relinks += 1
    else:
        # one-shot canonical refixpoint over the FULL chain multiset.
        # NOTE: continuing the fixpoint from the resolved state with only
        # the new chains is NOT sound — L ties break by arrival history,
        # and confluence is only pinned for peel-order link streams (a
        # late low-core link can re-merge components whose subsumed L
        # candidates are no longer re-presented); bowtie_plus randomized
        # sequences catch the discrepancy
        p0 = np.arange(n_r, dtype=np.int64)
        L0 = np.full((n_r,), -1, np.int64)
        la, lb = _chains(inc, core_new)
        stats.full_relinks += 1
        parent_new, L_new = _run_fixpoint(p0, L0, core_new, la, lb, hook)
    return new_p, core_new, parent_new, L_new


def update_decomposition(dec, delta: GraphDelta, *,
                         bucket_hook: Hook = None):
    """Apply ``delta`` to a live ``Decomposition``; returns
    ``(new_decomposition, UpdateStats)``.

    Requirements (actionable errors otherwise): ``method='exact'``,
    ``hierarchy`` in {'fused', 'none'}, (r, s) in ``SUPPORTED_RS``, and
    the ``NucleusProblem`` still attached.  ``order_round``/``rounds``
    are global-peel trace artifacts a local update cannot reproduce; the
    returned artifact carries ``order_round=None`` (like the sharded
    backend) and the ``rounds=-1`` sentinel.
    """
    from .api import Decomposition

    config = dec.config
    if config.method != "exact":
        raise ValueError(
            "update() maintains exact decompositions only (approximate "
            "peel values are trace artifacts, not a local fixpoint); "
            "re-run decompose() for approx artifacts")
    if (config.r, config.s) not in SUPPORTED_RS:
        raise ValueError(
            f"update() supports (r, s) in {SUPPORTED_RS}; got "
            f"({config.r}, {config.s}) — run a full decompose() instead")
    if config.hierarchy not in ("fused", "none"):
        raise ValueError(
            "update() patches the fused join forest (or none); "
            f"hierarchy={config.hierarchy!r} artifacts must re-decompose")
    if dec.problem is None:
        raise ValueError(
            "update() needs the NucleusProblem attached; a deserialized "
            "Decomposition has no incidence structure to maintain — "
            "re-decompose the edited graph instead")
    problem = dec.problem
    core = np.asarray(dec.core, np.int64).copy()
    parent = L = None
    if config.hierarchy == "fused":
        parent = np.asarray(dec.uf_parent, np.int64).copy()
        L = np.asarray(dec.uf_L, np.int64).copy()
    stats = UpdateStats()
    for op, u, v in delta.ops():
        stats.ops += 1
        problem, core, parent, L = _apply_op(problem, core, parent, L,
                                             op, u, v, stats, bucket_hook)
    core32 = jnp.asarray(core.astype(np.int32))
    out = Decomposition(
        config, problem=problem, core=core32, rounds=-1,
        order_round=None, peel_value=core32,
        uf_parent=None if parent is None
        else jnp.asarray(parent.astype(np.int32)),
        uf_L=None if L is None else jnp.asarray(L.astype(np.int32)),
        plan=dec.plan,
        # live-artifact identity: the successor keeps the published name
        # and advances one edit generation (what a routed status endpoint
        # reports as the artifact's version)
        name=dec.name, version=dec.version + 1)
    out.update_stats = stats
    return out, stats
