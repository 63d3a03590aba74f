"""The unified peel engine: ONE fixed-shape peel-round body for every backend.

``peel_round`` is the single implementation of a peel round (DESIGN.md
§"Engine"); ``run_peel_engine`` drives it with a ``lax.while_loop`` so the
whole peel compiles to one XLA computation — no per-round host sync, no
eager dispatch.  The same body serves:

  * the single-device dense backend (``peel.exact_coreness(backend="dense")``
    delegates here, one jitted call per problem shape), and
  * the ``shard_map`` distributed backend (``repro.core.distributed`` wraps
    the body with a psum ``reduce_delta`` hook; s-clique slabs are local,
    r-clique state replicated).

The loop carry records the **peel trace** on device: ``order_round[i]`` is
the round at which r-clique i peeled and ``core[i]`` the (raw, unclipped)
bucket value assigned to it.  The trace is information-equivalent to the old
per-round ``collect_links`` host callback (A_t = {i : order_round[i] == t},
peel values are the callback's core snapshot), so ANH-EL hierarchy
construction replays it post-hoc (``interleaved.replay_trace``) and coreness
stays a single compiled call.

The scatter-decrement hot path (count destroyed incidence per r-clique) has
two implementations: XLA ``.at[].add`` (the interpret/oracle fallback, and
the default off-TPU) and a Pallas sorted-segment-sum over the CSR edge array
(``kernels.segment_sum``), whose one-hot contraction runs on the MXU instead
of serialized scatter-adds.

With ``hierarchy=True`` the engine additionally threads the ANH-EL LINK
state (same-core union-find ``parent``, nearest-lower-core table ``L``,
per-s-clique ``last_peeled`` representative) through the while_loop carry:
each round materializes its chain-reduced link multiset (``round_links``)
and converges it with a batched fixpoint (``link_fixpoint``) — so ONE
compiled call returns coreness *and* the join forest, with the host trace
replay (``interleaved.replay_trace``) kept as the cross-check oracle.
DESIGN.md §5 has the carry layout and the termination argument.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from math import comb
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..graph import INT
from ..graph.connectivity import _varying
from ..graph.unionfind import uf_union_edges
from ..kernels.peel_round import (chunk_windows, fused_peel_round,
                                  peel_round_plan)
from ..kernels.segment_sum import (DEFAULT_BLOCK_N, DEFAULT_CHUNK_E,
                                   segment_sum_sorted, sorted_ids_plan)
from .backends import ConfigError
from .incidence import NucleusProblem
from .schedule import PeelSchedule

BIG = np.iinfo(np.int32).max
# the link fixpoint's counts, in the order of its ``counts`` vector
FIXPOINT_COUNTS = ("fixpoint_gens", "live_links", "link_refills")


def make_schedule(problem: NucleusProblem, kind: str,
                  delta: float = 0.1) -> PeelSchedule:
    return PeelSchedule(kind=kind, s_choose_r=comb(problem.s, problem.r),
                        delta=delta, n=problem.g.n)


def pallas_by_default() -> bool:
    """THE default-kernel policy: what ``use_pallas=None`` resolves to.

    The planner profile's measured verdict for this device
    (``planner_profile.use_pallas_default``, keyed by device kind, then
    platform), else Pallas on TPU.  ``dense_coreness`` and
    ``core.session`` both resolve through here."""
    from .planner_profile import use_pallas_default
    return use_pallas_default(jax.default_backend(),
                              jax.devices()[0].device_kind)


@dataclasses.dataclass(frozen=True)
class ScatterSpec:
    """Static (hashable) config of the Pallas scatter-decrement path."""

    block_n: int
    chunk_e: int
    max_chunks: int
    n_seg_pad: int
    interpret: bool


def scatter_decrement(inc_rid: jnp.ndarray, dead_now: jnp.ndarray,
                      n_r: int) -> jnp.ndarray:
    """delta[r] = # of s-cliques dying this round that contain r.

    XLA scatter-add formulation — the oracle the Pallas path is checked
    against, and the default backend off-TPU.  Rows with negative ids
    (distributed ghost padding) never contribute.
    """
    members = jnp.clip(inc_rid, 0, n_r - 1).reshape(-1)
    valid = ((inc_rid >= 0) & dead_now[:, None]).reshape(-1)
    return jnp.zeros((n_r,), INT).at[members].add(valid.astype(INT))


# ---------------------------------------------------------------------------
# Fused ANH-EL link state (DESIGN.md §5): fixed-shape link generation + the
# batched LINK-EFFICIENT fixpoint, pure jnp so it nests inside the peel loop.
# ---------------------------------------------------------------------------

def round_links(inc_rid, a_mask, last_peeled):
    """Chain-reduced ANH-EL link multiset for one peel round, fixed shape.

    Per s-clique row: members peeled this round (A ∩ S) are moved to the
    front by a stable sort and linked consecutively (the chain reduction of
    DESIGN.md §3); the chain head additionally hooks to the s-clique's
    previously peeled representative.  Matches ``interleaved._round_links``
    link-for-link, but emitted densely over all rows with a validity mask —
    untouched rows (no A-member) contribute nothing and keep last_peeled.

    Returns (la, lb, lvalid) of shape (n_s * C,) and the updated
    last_peeled.  Ghost rows (inc_rid < 0, distributed padding) never emit.
    """
    n_s, C = inc_rid.shape
    n_r = a_mask.shape[0]
    am = (inc_rid >= 0) & a_mask[jnp.clip(inc_rid, 0, n_r - 1)]
    order = jnp.argsort(~am, axis=1, stable=True)
    mem_s = jnp.take_along_axis(inc_rid, order, axis=1)
    am_s = jnp.take_along_axis(am, order, axis=1)
    cnt = am_s.sum(axis=1)
    # chain: A-members are a prefix after the sort, link consecutive pairs
    chain_valid = am_s[:, 1:]
    # head of each chain hooks to the previous representative of S (if any)
    head = mem_s[:, 0]
    prev = last_peeled
    head_valid = (prev >= 0) & (cnt > 0)
    last_peeled = jnp.where(cnt > 0, head, last_peeled)
    la = jnp.concatenate([mem_s[:, :-1].reshape(-1), prev])
    lb = jnp.concatenate([mem_s[:, 1:].reshape(-1), head])
    lvalid = jnp.concatenate([chain_valid.reshape(-1), head_valid])
    return la, lb, lvalid, last_peeled


def fixpoint_width(n_r: int, n_links: int) -> int:
    """The link fixpoint's worklist width: one slot per r-clique, never
    more than the links it is handed.  A generation already does n_r-wide
    work (``parent``, ``L``, the best tables), and its live count never
    rises (DESIGN.md §5), so n_r slots hold every generation's successors
    and handoffs; links beyond them wait in the pending list."""
    return min(n_r, n_links)


def link_fixpoint(parent, L, core, la, lb, lvalid, *,
                  max_gens: Optional[int] = None,
                  _width: Optional[int] = None):
    """Batched LINK-EFFICIENT fixpoint over one round's links, pure jnp.

    The numpy worklist (``interleaved.LinkState.process_links``) with fixed
    shapes.  The valid input links are compacted, in order, into a pending
    list; the worklist has ``fixpoint_width(n_r, K)`` slots (``_width``
    stands in for n_r, for tests) and takes pending links into its free
    slots, first at the start and again after every generation.  Each
    generation

      1. resolves + orients every link so core[a] <= core[b];
      2. unions same-core links with min-hooking (``uf_union_edges``, dead
         slots masked to self-edges), keeping ``parent`` fully resolved;
      3. lower-core links compete for L[target] by (max core, min id) via a
         two-pass scatter; every losing candidate re-links against the
         winner *in place* (slot i's successor overwrites slot i), and the
         ousted previous L re-links from the winning slot;
      4. roots absorbed by the union hand their L off as a fresh link
         (L[j], new root) into the free slots, placed by a cumsum over the
         losers and one over the free slots; pending links fill the rest.

    A generation's successors and handoffs never outnumber its live links
    (a handoff needs a merge, a merge an equal-core link), so the handoffs
    always fit.  Refilling only changes the arrival order of the same link
    multiset, and min-hooking and the (max core, min id) winner rule are
    confluent over it, so the final (parent, L) is the same resolved state
    the host replay computes.  Progress argument (DESIGN.md §5): unions are
    bounded by n_r - 1, every surviving successor strictly lowers
    core[target], and a generation that ends with nothing live is followed
    by one that takes at least one pending link, so the default max_gens
    (3·n_r + 4 + K) is a lowering bound that never binds.

    Returns (parent, L, counts), ``counts`` the int vector named by
    ``FIXPOINT_COUNTS``: the generations the loop ran, the live worklist
    slots summed over them, and the generations after the first that took
    pending links.  The loop carries the live count its condition tests,
    so counting adds no pass over the worklist.
    """
    n_r = parent.shape[0]
    K = la.shape[0]
    if n_r == 0 or K == 0:
        return parent, L, jnp.zeros((len(FIXPOINT_COUNTS),), INT)
    W = fixpoint_width(n_r if _width is None else _width, K)
    if max_gens is None:
        max_gens = 3 * n_r + 4 + K
    node = jnp.arange(n_r, dtype=INT)
    idx = jnp.arange(W, dtype=INT)
    # -- pending list: the valid links' slots, in order, in one cumsum +
    #    scatter
    n_pend = jnp.sum(lvalid, dtype=INT)
    dst = jnp.where(lvalid, jnp.cumsum(lvalid, dtype=INT) - 1, K)
    pend = jnp.zeros((K,), INT).at[dst].set(jnp.arange(K, dtype=INT),
                                            mode="drop")

    def cond(st):
        n_live, gen = st[6], st[7]
        return (n_live > 0) & (gen < max_gens)

    def body(st):
        parent, L, wa, wb, wv, taken, n_live, gen, live, refills = st
        # resolve (parent is fully resolved: one gather) and orient
        a = parent[jnp.clip(wa, 0, n_r - 1)]
        b = parent[jnp.clip(wb, 0, n_r - 1)]
        swap = core[a] > core[b]
        a, b = jnp.where(swap, b, a), jnp.where(swap, a, b)
        wv = wv & (a != b)
        eq = wv & (core[a] == core[b])
        # -- same-core union: min-hooking seeded by the current forest
        parent = uf_union_edges(parent, jnp.where(eq, a, 0),
                                jnp.where(eq, b, 0))
        # -- losers (roots absorbed just now) hand their L to the new root
        lost = (L >= 0) & (parent != node)
        Lc = jnp.where(lost, -1, L)
        # -- lower-core links install into L by (max core, min id)
        lt = wv & ~eq
        a = parent[a]  # roots may have moved in the union step
        b = parent[b]
        tgt = jnp.where(lt, b, n_r)          # slot n_r = dummy row
        cv = jnp.where(lt, a, 0)
        Lval = jnp.clip(Lc, 0, n_r - 1)
        Lhas = Lc >= 0
        best_core = (jnp.full((n_r + 1,), -1, INT)
                     .at[tgt].max(jnp.where(lt, core[cv], -1))
                     .at[jnp.where(Lhas, node, n_r)]
                     .max(jnp.where(Lhas, core[Lval], -1)))
        is_best = lt & (core[cv] == best_core[tgt])
        old_best = Lhas & (core[Lval] == best_core[:n_r])
        best_id = (jnp.full((n_r + 1,), BIG, INT)
                   .at[jnp.where(is_best, tgt, n_r)]
                   .min(jnp.where(is_best, cv, BIG))
                   .at[jnp.where(old_best, node, n_r)]
                   .min(jnp.where(old_best, Lval, BIG)))
        newL = jnp.where(best_id[:n_r] < BIG, best_id[:n_r], Lc)
        # -- successors: losing candidates re-link against their winner
        w_t = best_id[tgt]
        is_win = lt & (cv == w_t)
        rep = (jnp.full((n_r + 1,), W, INT)
               .at[jnp.where(is_win, tgt, n_r)]
               .min(jnp.where(is_win, idx, W)))
        host = is_win & (idx == rep[tgt])
        succ_a = jnp.where(host, Lc[jnp.clip(tgt, 0, n_r - 1)], cv)
        succ_v = lt & (succ_a >= 0) & (succ_a != w_t)
        # -- free slots take the handoffs (L[j], parent[j]) of the losers
        #    in node order, then pending links in list order
        free = ~succ_v
        rank = jnp.cumsum(free, dtype=INT) - 1
        n_lost = jnp.sum(lost, dtype=INT)
        lost_at = jnp.where(lost, jnp.cumsum(lost, dtype=INT) - 1, W)
        lost_id = jnp.zeros((W,), INT).at[lost_at].set(node, mode="drop")
        j = lost_id[jnp.clip(rank, 0, W - 1)]
        hand = free & (rank < n_lost)
        src = taken + rank - n_lost
        take = free & ~hand & (src < n_pend)
        s = pend[jnp.clip(src, 0, K - 1)]
        wa = jnp.where(hand, L[j], jnp.where(take, la[s], succ_a))
        wb = jnp.where(hand, parent[j], jnp.where(take, lb[s], w_t))
        wv = succ_v | hand | take
        n_take = jnp.sum(take, dtype=INT)
        return (parent, newL, wa, wb, wv, taken + n_take,
                jnp.sum(wv, dtype=INT), gen + 1, live + n_live,
                refills + (n_take > 0))

    # the round's first fill; every carry typed like the data it meets
    wv = idx < n_pend
    first = pend[:W]
    st = (parent, L, la[first], lb[first], wv, jnp.minimum(n_pend, W),
          jnp.sum(wv, dtype=INT), jnp.zeros((), INT), jnp.zeros((), INT),
          jnp.zeros((), INT))
    st = tuple(_varying(x, parent, L, core, la, lb, pend) for x in st)
    parent, L, _, _, _, _, _, gens, live, refills = jax.lax.while_loop(
        cond, body, st)
    return parent, L, jnp.stack([gens, live, refills])


# ---------------------------------------------------------------------------
# Restartable local convergence (DESIGN.md §10): the h-operator Jacobi sweep
# the streaming update path runs over an affected subproblem.  Unlike the
# peel (which starts from scratch), these entries start from a caller-
# provided value state and iterate DOWNWARD to the largest fixpoint below
# it — which equals the exact core values whenever the seed dominates them
# pointwise and the frozen boundary carries its true values (the local
# h-index characterization of Sarıyüce–Seshadhri–Pınar, arXiv 1704.00386).
# ---------------------------------------------------------------------------

def h_index_rows(vals: jnp.ndarray) -> jnp.ndarray:
    """Row-wise h-index: the largest h with >= h entries >= h.

    Negative entries are padding sentinels and never count (they cannot
    satisfy ``>= h`` for any h >= 1)."""
    d = vals.shape[1]
    if d == 0:
        return jnp.zeros((vals.shape[0],), INT)
    desc = -jnp.sort(-vals, axis=1)
    ks = jnp.arange(1, d + 1, dtype=INT)[None, :]
    return jnp.max(jnp.where(desc >= ks, ks, 0), axis=1)


@jax.jit
def local_converge(inc_sub, gather_idx, vals0, frozen, max_sweeps):
    """Restartable-from-state h-operator iteration over a padded subproblem.

    One Jacobi sweep computes, for every r-clique i of the subproblem,
    Theta(f)[i] = h-index over { min_{j in S, j != i} f[j] : S an incident
    s-clique }, then applies f <- min(f, Theta(f)) on the non-frozen
    entries; the loop runs until a sweep changes nothing.  Theta is
    monotone, so the iteration converges to the largest fixpoint below the
    seed (Tarski) — the exact core values when the seed dominates them and
    the frozen ring carries its true values (DESIGN.md §10).

    inc_sub:    (rows, C) member indices into the subproblem's r-clique
                space; fully -1 rows are padding.
    gather_idx: (m, d) flat indices into the (rows * C) incidence slots
                owned by each r-clique; ``rows * C`` is the sentinel slot
                (reads -1, which the h-index ignores).
    vals0:      (m,) seed values; frozen entries are boundary state.
    max_sweeps: traced scalar safety cap (each productive sweep lowers the
                integer total by >= 1, so sum(seed) + 2 always suffices).

    Shapes are the jit key: the streaming path pads (rows, m, d) to pow2
    buckets so a stream of updates reuses one executable per shape class.
    Returns (vals, sweeps).
    """
    m = vals0.shape[0]
    n_slots = inc_sub.shape[0] * inc_sub.shape[1]
    colv = jnp.arange(inc_sub.shape[1], dtype=INT)[None, :]

    def theta(vals):
        va = jnp.where(inc_sub >= 0, vals[jnp.clip(inc_sub, 0, m - 1)], BIG)
        m1 = jnp.min(va, axis=1)
        am = jnp.argmin(va, axis=1).astype(INT)
        m2 = jnp.min(jnp.where(colv == am[:, None], BIG, va), axis=1)
        # min over the OTHER members: the unique argmin column sees the
        # second-smallest, every other column sees the row minimum
        excl = jnp.where(colv == am[:, None], m2[:, None], m1[:, None])
        rv = jnp.where(inc_sub >= 0, excl, -1).reshape(-1)
        rv = jnp.concatenate([rv, jnp.full((1,), -1, INT)])
        cand = rv[jnp.clip(gather_idx, 0, n_slots)]
        return h_index_rows(cand)

    def cond(st):
        _, done, sweeps = st
        return (~done) & (sweeps < max_sweeps)

    def body(st):
        vals, _, sweeps = st
        new = jnp.where(frozen, vals, jnp.minimum(vals, theta(vals)))
        return new, jnp.all(new == vals), sweeps + 1

    vals, _, sweeps = jax.lax.while_loop(
        cond, body, (vals0, jnp.zeros((), bool), jnp.zeros((), INT)))
    return vals, sweeps


def peel_round(inc_rid, deg, peeled, s_alive, core, order_round, sched,
               rounds, schedule: PeelSchedule, *,
               reduce_delta: Optional[Callable] = None, resid=None,
               scatter: Optional[Callable] = None,
               fused_round: Optional[Callable] = None):
    """THE peel-round body — every backend runs exactly this.

    inc_rid: (n_s_local, C) member r-clique ids (-1 rows = ghost padding);
    deg/peeled/core/order_round: (n_r,) replicated r-clique state; s_alive:
    (n_s_local,) local s-clique liveness; sched: schedule carry; rounds: the
    round counter recorded into the trace.

    reduce_delta(delta, resid) -> (delta, resid) is the distributed
    all-reduce hook (identity when None); scatter(dead_now) -> (n_r,) delta
    overrides the decrement implementation (Pallas scatter path).  The
    round's peeled set a_mask is returned so the fused hierarchy path can
    generate its links without recomputing the bucket.

    fused_round(deg, peeled, core, order, level, rounds) -> (deg, peeled,
    core, order) replaces the ENTIRE select + gather + decrement chain
    (the Pallas round megakernel, or the r1s2 vertex-peel fast lane); the
    schedule advance and dmin reduction stay here, s_alive passes through
    untouched (the megakernel derives liveness from ``peeled``, DESIGN.md
    §9), and a_mask is recovered from the peeled delta.
    """
    n_r = deg.shape[0]
    live_deg = jnp.where(peeled, BIG, deg)
    dmin = jnp.min(live_deg)
    sched, level = schedule.next_level(sched, dmin)
    if fused_round is not None:
        deg, peeled_new, core, order_round = fused_round(
            deg, peeled, core, order_round, level, rounds)
        a_mask = peeled_new & ~peeled
        return (deg, peeled_new, s_alive, core, order_round, sched, resid,
                a_mask)
    a_mask = (~peeled) & (deg <= level)
    core = jnp.where(a_mask, level, core)
    order_round = jnp.where(a_mask, rounds, order_round)
    peeled = peeled | a_mask
    member_peeled = peeled[jnp.clip(inc_rid, 0, n_r - 1)] | (inc_rid < 0)
    dead_now = jnp.any(member_peeled, axis=1) & s_alive
    s_alive = s_alive & ~dead_now
    if scatter is None:
        delta = scatter_decrement(inc_rid, dead_now, n_r)
    else:
        delta = scatter(dead_now)
    if reduce_delta is not None:
        delta, resid = reduce_delta(delta, resid)
    # peeled cliques keep deg frozen (their core is already assigned)
    deg = jnp.where(peeled, deg, deg - delta)
    return deg, peeled, s_alive, core, order_round, sched, resid, a_mask


def run_peel_engine(inc_rid, deg0, schedule: PeelSchedule, *,
                    max_rounds: int,
                    reduce_delta: Optional[Callable] = None,
                    resid0=None, alive0=None,
                    scatter: Optional[Callable] = None,
                    fused_round: Optional[Callable] = None,
                    hierarchy: bool = False, link0=None,
                    gather_links: Optional[Callable] = None,
                    peeled0=None, link_width: Optional[int] = None):
    """Drive ``peel_round`` to a fixpoint under one ``lax.while_loop``.

    Returns (core, order_round, rounds, counts): raw bucket values per
    r-clique, the on-device peel trace, the round count, and the link
    fixpoint's ``counts`` vector (``FIXPOINT_COUNTS``) summed over the
    rounds (zeros without hierarchy).  Every
    round peels at least one clique (the schedule guarantees level >=
    dmin), so the loop runs at most n_r rounds; max_rounds is a static
    safety cap for lowering.

    hierarchy=True additionally threads the fused ANH-EL state through the
    carry and appends (parent, L) — the resolved same-core join forest — to
    the return: one compiled call yields coreness AND hierarchy.  link0
    overrides the initial (parent, L, last_peeled) triple (the distributed
    backend passes device-varying-marked arrays); gather_links(la, lb,
    lvalid) all-gathers each round's locally generated links so the
    replicated link state sees the global multiset.  link_width forces
    the fixpoint's worklist width (``link_fixpoint``'s ``_width``; tests).

    peeled0 marks r-cliques as already peeled before round 0 — the ghost
    entries of a shape-bucketed padded problem (``core.session``).  They
    never enter a peel bucket, never drag the schedule minimum (live
    degree is masked to BIG), emit no links and keep core/order at -1, so
    the real prefix of every output is bit-identical to the unpadded run.
    """
    n_r = deg0.shape[0]
    core0 = jnp.full((n_r,), -1, INT)
    order0 = jnp.full((n_r,), -1, INT)
    zero = jnp.zeros((), INT)
    counts0 = jnp.zeros((len(FIXPOINT_COUNTS),), INT)
    if n_r == 0:
        if hierarchy:
            empty = jnp.zeros((0,), INT)
            return core0, order0, zero, counts0, empty, empty
        return core0, order0, zero, counts0
    peeled0 = jnp.zeros((n_r,), bool) if peeled0 is None else peeled0
    if alive0 is None:
        alive0 = jnp.ones((inc_rid.shape[0],), bool)
    if resid0 is None:
        resid0 = jnp.zeros((1,), INT)
    if hierarchy and link0 is None:
        link0 = (jnp.arange(n_r, dtype=INT), jnp.full((n_r,), -1, INT),
                 jnp.full((inc_rid.shape[0],), -1, INT))
    if hierarchy:
        # the fixpoint's counts ride with the link state, typed like it
        link0 = tuple(link0) + (_varying(counts0, link0[0]),)
    else:
        link0 = ()
    sched0 = schedule.init_carry()
    rounds0 = jnp.zeros((), INT)

    def cond(carry):
        peeled, rounds = carry[1], carry[6]
        return (~jnp.all(peeled)) & (rounds < max_rounds)

    def body(carry):
        deg, peeled, alive, core, order, sched, rounds, resid = carry[:8]
        with jax.named_scope("repro.peel_round"):
            deg, peeled, alive, core, order, sched, resid, a_mask = \
                peel_round(inc_rid, deg, peeled, alive, core, order, sched,
                           rounds, schedule, reduce_delta=reduce_delta,
                           resid=resid, scatter=scatter,
                           fused_round=fused_round)
        link = carry[8:]
        # no s-cliques -> no links ever; also keeps all_gather away from
        # zero-size operands (XLA rejects an empty all_gather dim)
        if hierarchy and inc_rid.shape[0] > 0:
            parent, L, last, counts = link
            with jax.named_scope("repro.round_links"):
                la, lb, lv, last = round_links(inc_rid, a_mask, last)
            if gather_links is not None:
                la, lb, lv = gather_links(la, lb, lv)
            with jax.named_scope("repro.link_fixpoint"):
                parent, L, c = link_fixpoint(parent, L, core, la, lb, lv,
                                             _width=link_width)
            link = (parent, L, last, counts + c)
        return (deg, peeled, alive, core, order, sched, rounds + 1,
                resid) + link

    carry = (deg0, peeled0, alive0, core0, order0, sched0, rounds0,
             resid0) + link0
    out = jax.lax.while_loop(cond, body, carry)
    core, order, rounds = out[3], out[4], out[6]
    if hierarchy:
        parent, L, _, counts = out[8:]
        return core, order, rounds, counts, parent, L
    return core, order, rounds, counts0


# ---------------------------------------------------------------------------
# Single-device dense backend: jitted entry + Pallas scatter plan
# ---------------------------------------------------------------------------

def round_up_pow2_chunks(max_chunks: int, n_chunks: int) -> int:
    """The chunk-span bound rounded up to a power of two (floor 8, capped
    at the plan's chunk count) so it stops fragmenting a bucket's jit key."""
    mc = max(max_chunks, 8)
    mc = 1 << (mc - 1).bit_length()
    return max(min(mc, n_chunks), 1)


@partial(jax.jit, static_argnames=("schedule", "max_rounds", "spec",
                                   "hierarchy", "fused", "link_width"))
def _dense_engine(inc_rid, deg0, plan_a, plan_b, peeled0, *,
                  schedule: PeelSchedule, max_rounds: int,
                  spec: Optional[ScatterSpec], hierarchy: bool = False,
                  fused: bool = False, link_width: Optional[int] = None):
    """The jitted dense entry.  spec=None: pure-XLA round body.  spec set
    with fused=True: (plan_a, plan_b) = (ids, members) of the round
    megakernel — one Pallas launch replaces the whole select + gather +
    decrement chain.  spec set with fused=False: (plan_a, plan_b) =
    (rids, sids) of the scatter-only Pallas path (the decrement alone).
    link_width forces the link fixpoint's worklist width (tests).
    Returns ``run_peel_engine``'s outputs."""
    n_r = deg0.shape[0]
    scatter = None
    fused_round = None
    if spec is not None and fused:
        ids, members = plan_a, plan_b
        # loop-invariant per-block chunk windows: computed once out here,
        # closed over by every round's kernel launch
        c0, nch = chunk_windows(ids, spec.n_seg_pad, spec.block_n,
                                spec.chunk_e, spec.max_chunks)
        pad = spec.n_seg_pad - n_r

        def fused_round(deg, peeled, core, order, level, rnd):
            degp = jnp.concatenate([deg, jnp.zeros((pad,), INT)])
            peeledp = jnp.concatenate(
                [peeled.astype(INT), jnp.ones((pad,), INT)])
            corep = jnp.concatenate([core, jnp.full((pad,), -1, INT)])
            orderp = jnp.concatenate([order, jnp.full((pad,), -1, INT)])
            d, p, c, o = fused_peel_round(
                ids, members, degp, peeledp, corep, orderp, level, rnd,
                c0, nch, block_n=spec.block_n, chunk_e=spec.chunk_e,
                max_chunks=spec.max_chunks, interpret=spec.interpret)
            return d[:n_r], p[:n_r] > 0, c[:n_r], o[:n_r]
    elif spec is not None:
        plan_rids, plan_sids = plan_a, plan_b

        def scatter(dead_now):
            # lane-major (1, E): an (E, 1) operand is padded 128-fold on TPU
            data = dead_now[plan_sids].astype(INT)[None, :]
            out = segment_sum_sorted(data, plan_rids, spec.n_seg_pad,
                                     block_n=spec.block_n,
                                     chunk_e=spec.chunk_e,
                                     max_chunks=spec.max_chunks,
                                     interpret=spec.interpret)
            return out[0, :n_r]
    return run_peel_engine(inc_rid, deg0, schedule, max_rounds=max_rounds,
                           scatter=scatter, fused_round=fused_round,
                           hierarchy=hierarchy, peeled0=peeled0,
                           link_width=link_width)


def _scatter_plan(problem: NucleusProblem, block_n: int, chunk_e: int,
                  interpret: bool, *, e_pad: Optional[int] = None,
                  n_r_pad: Optional[int] = None, pow2_chunks: bool = False):
    """CSR edge arrays (rid-sorted) padded for the Pallas segment sum.

    Edge k of the flat CSR is (rid=plan_rids[k], sid=plan_sids[k]) with
    plan_rids ascending — exactly what ``segment_sum_sorted`` wants; the
    per-round data vector is just ``dead_now[plan_sids]``.  Built once per
    (problem, kernel tiling) and memoized on the problem: the O(E) host
    expansion + device upload must not recur on every coreness call.
    The optional pad overrides let ``core.session`` shape the plan to its
    pow2 buckets so same-bucket problems share one executable;
    ``pow2_chunks`` rounds the chunk-span bound (``round_up_pow2_chunks``).
    """
    key = (block_n, chunk_e, interpret, e_pad, n_r_pad, pow2_chunks)
    cache = getattr(problem, "_scatter_plans", None)
    if cache is None:
        cache = {}
        problem._scatter_plans = cache
    if key in cache:
        return cache[key]
    counts = np.diff(np.asarray(problem.mem_offsets))
    rids = np.repeat(np.arange(problem.n_r, dtype=np.int32), counts)
    rids_pad, n_seg_pad, max_chunks = sorted_ids_plan(
        rids, problem.n_r, block_n=block_n, chunk_e=chunk_e, e_pad=e_pad,
        n_seg_pad=n_r_pad)
    if pow2_chunks:
        max_chunks = round_up_pow2_chunks(max_chunks,
                                          rids_pad.shape[0] // chunk_e)
    sids_pad = np.zeros(rids_pad.shape[0], np.int32)
    sids_pad[:rids.shape[0]] = np.asarray(problem.mem_sids, np.int32)
    spec = ScatterSpec(block_n=block_n, chunk_e=chunk_e,
                       max_chunks=max_chunks, n_seg_pad=n_seg_pad,
                       interpret=interpret)
    cache[key] = (jnp.asarray(rids_pad), jnp.asarray(sids_pad), spec)
    return cache[key]


def _plan_arrays(problem: NucleusProblem):
    """(rids, members) of the rid-sorted CSR edge plan, eager numpy."""
    counts = np.diff(np.asarray(problem.mem_offsets))
    rids = np.repeat(np.arange(problem.n_r, dtype=np.int32), counts)
    members = np.asarray(problem.inc_rid)[np.asarray(problem.mem_sids,
                                                     np.int64)]
    return rids, members


def _round_plan(problem: NucleusProblem, block_n: int, chunk_e: int,
                interpret: bool):
    """Megakernel plan: (ids, members, spec), memoized on the problem.

    Edge k of the flat CSR is rid ``ids[k]`` inside the s-clique whose full
    member row is ``members[k]`` — everything the fused dead test needs,
    gathered once at plan-build time.
    """
    key = ("round", block_n, chunk_e, interpret)
    cache = getattr(problem, "_scatter_plans", None)
    if cache is None:
        cache = {}
        problem._scatter_plans = cache
    if key in cache:
        return cache[key]
    rids, members = _plan_arrays(problem)
    ids_pad, members_pad, n_r_pad, max_chunks = peel_round_plan(
        rids, members, problem.n_r, block_n=block_n, chunk_e=chunk_e)
    spec = ScatterSpec(block_n=block_n, chunk_e=chunk_e,
                       max_chunks=max_chunks, n_seg_pad=n_r_pad,
                       interpret=interpret)
    cache[key] = (jnp.asarray(ids_pad), jnp.asarray(members_pad), spec)
    return cache[key]


def dense_coreness(problem: NucleusProblem, schedule: PeelSchedule, *,
                   use_pallas: Optional[bool] = None,
                   max_rounds: Optional[int] = None,
                   block_n: int = DEFAULT_BLOCK_N,
                   chunk_e: int = DEFAULT_CHUNK_E,
                   interpret: Optional[bool] = None,
                   hierarchy: bool = False,
                   peeled0=None,
                   plan=None,
                   fused_kernel: Optional[bool] = None):
    """One jitted call: (core_raw, order_round, rounds, counts) for the
    whole peel; ``counts`` is the link fixpoint's ``FIXPOINT_COUNTS``
    vector summed over the rounds, zeros without hierarchy
    (``run_peel_engine``).

    use_pallas=None resolves through ``pallas_by_default()`` — the planner
    profile's measured verdict when one covers this device, else Pallas on
    TPU (Pallas interpret mode is a correctness oracle, not a fast path).
    Raw bucket values are returned — approx clipping is the caller's job
    so the trace keeps the values that drove LINK equality.

    With Pallas on, the round body is the scatter-only Pallas plan: the
    decrement runs as a sorted-segment sum over the rid-sorted CSR edge
    array.  ``fused_kernel=True`` selects the round MEGAKERNEL
    (``kernels.peel_round``) instead; it runs in interpret mode only and
    raises ``ConfigError`` when it would be compiled.
    ``plan=(rids, sids, spec)`` injects a prebuilt scatter plan —
    ``core.session`` passes its pow2-bucketed plan so warm calls share one
    executable.

    hierarchy=True fuses the ANH-EL link fixpoint into the same compiled
    call and appends the join forest (parent, L) to the return tuple.

    peeled0 pre-peels ghost r-cliques of a shape-bucketed padded problem
    (``core.session``); it is always materialized to an array before the
    jit call so the executable cache keys only on shapes + statics.
    """
    if use_pallas is None:
        use_pallas = pallas_by_default()
    if max_rounds is None:
        max_rounds = problem.n_r + 2
    dummy = jnp.zeros((0,), INT)
    plan_a, plan_b, spec = dummy, dummy, None
    fused = False
    if use_pallas and problem.n_s > 0:
        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        if fused_kernel and not interpret:
            # Mosaic (the TPU Pallas compiler) lowers only 2-D in-kernel
            # gathers; the megakernel gathers from a full 1-D state vector
            raise ConfigError(
                "fused_kernel=True: the round megakernel gathers r-clique "
                "state (peeled[members], deg[members]) from a full 1-D "
                "vector inside the kernel, and Mosaic lowers only 2-D "
                "gathers (NotImplementedError: Only 2D gather is "
                "supported), so it cannot compile for a TPU; it runs in "
                "interpret mode only.  Leave fused_kernel unset for the "
                "scatter-only Pallas plan.")
        if plan is not None:
            plan_a, plan_b, spec = plan
        elif fused_kernel:
            plan_a, plan_b, spec = _round_plan(problem, block_n, chunk_e,
                                               interpret)
            fused = True
        else:
            plan_a, plan_b, spec = _scatter_plan(problem, block_n, chunk_e,
                                                 interpret)
    if peeled0 is None:
        peeled0 = jnp.zeros((problem.deg0.shape[0],), bool)
    return _dense_engine(problem.inc_rid, problem.deg0, plan_a, plan_b,
                         peeled0, schedule=schedule, max_rounds=max_rounds,
                         spec=spec, hierarchy=hierarchy, fused=fused)
