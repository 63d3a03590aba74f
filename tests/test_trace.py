"""The program's host spans (``core.trace``), and the peel engine's loop
counters (``Decomposition.counters``).

Contracts:
  * COUNTERS — the Session's padded path and the plain ``dense_coreness``
    held to the same worklist width count the same fixpoint generations,
    live links and refills (ghost r-cliques emit no links); replayed
    round by round, each round's first generation holds the valid links
    ``round_links`` emits up to that width, a round refills exactly when
    they overflow it, and the per-round counts add up to the engine's.
  * CAPACITY — no generation of the fixpoint ends with more live links
    than it began with, so the handoffs always fit the freed slots.
  * SPANS — under a profiler, a routed job's host spans nest as the layers
    do and all carry the request id the Router assigned.
"""
import glob
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import NucleusConfig, build_problem, decompose, engine, trace
from repro.core.engine import (FIXPOINT_COUNTS, dense_coreness,
                               link_fixpoint, make_schedule, round_links)
from repro.core.session import Session
from repro.graph import generators
from repro.serve import Request, Router

pytestmark = pytest.mark.fast

@pytest.fixture(scope="module")
def graph():
    return generators.kronecker(5, seed=3)


@pytest.fixture(scope="module")
def problem(graph):
    return build_problem(graph, 2, 3, build="chunked")


@pytest.fixture(scope="module")
def plain(problem):
    """(core, order_round, rounds, parent, L, counts) of the unpadded
    engine run."""
    core, order, rounds, counts, parent, L = dense_coreness(
        problem, make_schedule(problem, "exact"), use_pallas=False,
        hierarchy=True)
    return core, order, rounds, parent, L, dict(
        zip(FIXPOINT_COUNTS, (int(x) for x in np.asarray(counts))))


def test_session_counters_match_the_plain_engine(problem, monkeypatch):
    dec = Session(use_pallas=False).decompose(problem)
    c = dec.counters
    assert (c["n_r"], c["n_s"]) == (problem.n_r, problem.n_s)
    assert c["n_r_pad"] >= c["n_r"] and c["n_s_pad"] >= c["n_s"]
    # the worklist is n_r_pad slots, never more than the n_s_pad·C links
    # a round emits; the plain engine held to that width counts the same
    # (ghost r-cliques emit no links)
    width = min(c["n_r_pad"], c["n_s_pad"] * problem.n_sub)
    monkeypatch.setattr(engine, "_dense_engine", partial(
        engine._dense_engine, link_width=width))
    counts = dense_coreness(problem, make_schedule(problem, "exact"),
                            use_pallas=False, hierarchy=True)[3]
    assert {k: c[k] for k in FIXPOINT_COUNTS} == dict(
        zip(FIXPOINT_COUNTS, (int(x) for x in np.asarray(counts))))
    assert c["fixpoint_gens"] >= 1
    # every generation passes the whole worklist
    assert c["link_slots"] == c["fixpoint_gens"] * width
    assert 0 < c["live_links"] <= c["link_slots"]
    assert 0 <= c["link_refills"] <= c["fixpoint_gens"]
    # without the fused hierarchy the fixpoint never runs
    bare = Session(use_pallas=False, hierarchy="none").decompose(problem)
    assert bare.counters == dict(c, fixpoint_gens=0, live_links=0,
                                 link_slots=0, link_refills=0)
    # the unbucketed path does not count
    assert decompose(problem, NucleusConfig(use_pallas=False)
                     ).counters is None


def _round_inputs(problem, plain):
    """Each round's (links, valid, core so far), from the engine's own
    trace, in peel order."""
    core, order, rounds = (np.asarray(x) for x in plain[:3])
    links = jax.jit(round_links)
    last = jnp.full((problem.n_s,), -1, jnp.int32)
    for t in range(int(rounds)):
        la, lb, lv, last = links(problem.inc_rid, jnp.asarray(order == t),
                                 last)
        yield la, lb, lv, jnp.asarray(
            np.where((order >= 0) & (order <= t), core, -1))


FIX = jax.jit(link_fixpoint, static_argnames=("max_gens", "_width"))
# the generation cap traced, so stepping it compiles once
FIX_STEPS = jax.jit(link_fixpoint, static_argnames="_width")


def test_round_replay_counts(problem, plain):
    """Each round's links through the counted fixpoint, one round at a
    time, from the engine's own trace: the first generation's live slots
    are the valid links up to the worklist's width, a round refills
    exactly when its links overflow that width, and the rounds' counts
    sum to the engine's."""
    counts = plain[5]
    n_r = problem.n_r
    width = min(n_r, problem.n_s * problem.n_sub)
    parent = jnp.arange(n_r, dtype=jnp.int32)
    L = jnp.full((n_r,), -1, jnp.int32)
    totals = np.zeros(len(FIXPOINT_COUNTS), np.int64)
    rounds_with_links = 0
    for t, (la, lb, lv, core_t) in enumerate(_round_inputs(problem, plain)):
        n_links = int(jnp.sum(lv))
        _, _, first = FIX(parent, L, core_t, la, lb, lv, max_gens=1)
        assert int(first[1]) == min(n_links, width), t
        parent, L, c = FIX(parent, L, core_t, la, lb, lv)
        gens, _, refills = (int(x) for x in c)
        assert (gens >= 1) == (n_links > 0), t
        assert (refills > 0) == (n_links > width), t
        rounds_with_links += n_links > 0
        totals += np.asarray(c)
    assert dict(zip(FIXPOINT_COUNTS, totals.tolist())) == counts
    assert rounds_with_links > 0
    assert counts["fixpoint_gens"] >= rounds_with_links
    np.testing.assert_array_equal(np.asarray(parent), np.asarray(plain[3]))


def _lives(parent, L, core, la, lb, lv, width):
    """Live worklist slots of each generation, by stepping the cap."""
    lives = []
    while True:
        _, _, c = FIX_STEPS(parent, L, core, la, lb, lv,
                            max_gens=jnp.int32(len(lives) + 1), _width=width)
        if int(c[0]) <= len(lives):
            return lives
        lives.append(int(c[1]) - sum(lives))


def _pad(la, lb, lv, n):
    """The links with ``n`` invalid slots appended."""
    z = jnp.zeros((n,), jnp.int32)
    return (jnp.concatenate([la, z]), jnp.concatenate([lb, z]),
            jnp.concatenate([lv, jnp.zeros((n,), bool)]))


def test_live_links_never_rise_within_a_generation(problem, plain):
    """The capacity argument behind the n_r-wide worklist, on the
    fixpoint itself: with room for every handoff (the links padded by one
    invalid slot per r-clique and a worklist as wide as all of them, so
    nothing waits and nothing is dropped), no generation ends with more
    live links than it began with.  At 8 slots the same rounds refill
    from the pending list and reach the same (parent, L)."""
    n_r = problem.n_r
    wide = problem.n_s * problem.n_sub + n_r
    parent = jnp.arange(n_r, dtype=jnp.int32)
    L = jnp.full((n_r,), -1, jnp.int32)
    refilled = 0
    for t, (la, lb, lv, core_t) in enumerate(_round_inputs(problem, plain)):
        lives = _lives(parent, L, core_t, *_pad(la, lb, lv, n_r), wide)
        assert lives == sorted(lives, reverse=True), (t, lives)
        p8, L8, c8 = FIX(parent, L, core_t, la, lb, lv, _width=8)
        parent, L, _ = FIX(parent, L, core_t, la, lb, lv)
        np.testing.assert_array_equal(np.asarray(p8), np.asarray(parent))
        np.testing.assert_array_equal(np.asarray(L8), np.asarray(L))
        assert int(c8[1]) <= 8 * int(c8[0]), t
        refilled += int(c8[2]) > 0
    assert refilled > 0
    np.testing.assert_array_equal(np.asarray(parent), np.asarray(plain[3]))
    # the tight case: each of k - 1 equal-core links absorbs a root that
    # holds an L, so the first generation's handoffs fill exactly the
    # slots its links freed, at any width down to the k - 1 links
    k = 6
    core = jnp.asarray([5] * k + [1, 2, 3, 4, 1, 2], jnp.int32)
    parent = jnp.arange(2 * k, dtype=jnp.int32)
    L = jnp.asarray(list(range(k, 2 * k)) + [-1] * k, jnp.int32)
    la = jnp.arange(k - 1, dtype=jnp.int32)
    lv = jnp.ones((k - 1,), bool)
    padded = _pad(la, la + 1, lv, 2 * k)
    lives = _lives(parent, L, core, *padded, 3 * k)
    assert lives[:2] == [k - 1, k - 1], lives
    assert lives == sorted(lives, reverse=True), lives
    wide_out = FIX(parent, L, core, *padded, _width=3 * k)
    tight_out = FIX(parent, L, core, la, la + 1, lv)
    for a, b in zip(wide_out[:2], tight_out[:2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _spans(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    return sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                    dict(ev.stats))
                   for plane in ProfileData.from_file(path).planes
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith(trace.PREFIX)),
                  key=lambda t: (t[0], -t[1]))


def test_router_job_spans_nest_and_share_the_request_id(graph, tmp_path):
    router = Router()
    assert trace.current() is None
    with jax.profiler.trace(str(tmp_path)):
        dec = router.route(Request(graph=graph, use_pallas=False,
                                   build="chunked"))
        dec.tree
        dec.nuclei(1)
    assert trace.current() is None
    spans = _spans(tmp_path)
    names = [n for _, _, n, _ in spans]
    parent = {}
    for i, (s, e, n, _) in enumerate(spans):
        enclosing = [m for t, f, m, _ in spans[:i] if t <= s and e <= f]
        parent[n] = enclosing[-1] if enclosing else None
    assert parent == {
        "repro.route": None,
        "repro.build": "repro.route",
        "repro.build.orient": "repro.build",
        "repro.build.count": "repro.build",
        "repro.build.fill": "repro.build",
        "repro.build.assemble": "repro.build",
        "repro.build.upload": "repro.build",
        "repro.session.decompose": "repro.route",
        "repro.session.pad": "repro.session.decompose",
        "repro.engine": "repro.session.decompose",
        "repro.engine.fetch": "repro.engine",
        "repro.tree": None,
        "repro.nuclei": None,
        "repro.cut": "repro.nuclei",
    }, names
    assert {a.get("rid") for _, _, _, a in spans} == {dec.request_id}
    engine, = [a for _, _, n, a in spans if n == "repro.engine"]
    assert engine["rounds"] == dec.rounds
    assert {k: engine[k] for k in dec.counters} == dec.counters
