"""The program's host spans (``core.trace``), and the peel engine's loop
counters (``Decomposition.counters``).

Contracts:
  * COUNTERS — the Session's padded path and the plain ``dense_coreness``
    count the same fixpoint generations and live links (ghost r-cliques
    emit no links); replayed round by round, each round's first
    generation holds exactly the valid links ``round_links`` emits, and
    the per-round counts add up to the engine's.
  * SPANS — under a profiler, a routed job's host spans nest as the layers
    do and all carry the request id the Router assigned.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import NucleusConfig, build_problem, decompose, trace
from repro.core.engine import (dense_coreness, link_fixpoint, make_schedule,
                               round_links)
from repro.core.session import Session
from repro.graph import generators
from repro.serve import Request, Router

pytestmark = pytest.mark.fast

FIXPOINT_COUNTS = ("fixpoint_gens", "live_links")


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
    core, order, rounds, gens, live, parent, L = dense_coreness(
        problem, make_schedule(problem, "exact"), use_pallas=False,
        hierarchy=True)
    return core, order, rounds, parent, L, {"fixpoint_gens": int(gens),
                                            "live_links": int(live)}


def test_session_counters_match_the_plain_engine(problem, plain):
    dec = Session(use_pallas=False).decompose(problem)
    c = dec.counters
    assert {k: c[k] for k in FIXPOINT_COUNTS} == plain[5]
    assert c["fixpoint_gens"] >= 1
    assert (c["n_r"], c["n_s"]) == (problem.n_r, problem.n_s)
    assert c["n_r_pad"] >= c["n_r"] and c["n_s_pad"] >= c["n_s"]
    # every generation passes the whole padded worklist
    assert c["link_slots"] == c["fixpoint_gens"] * (
        c["n_s_pad"] * problem.n_sub + c["n_r_pad"])
    assert 0 < c["live_links"] <= c["link_slots"]
    # without the fused hierarchy the fixpoint never runs
    bare = Session(use_pallas=False, hierarchy="none").decompose(problem)
    assert bare.counters == dict(c, fixpoint_gens=0, live_links=0,
                                 link_slots=0)
    # the unbucketed path does not count
    assert decompose(problem, NucleusConfig(use_pallas=False)
                     ).counters is None


def test_round_replay_counts(problem, plain):
    """Each round's links through the counted fixpoint, one round at a
    time, from the engine's own trace: the first generation's live slots
    are the valid links, and the rounds' counts sum to the engine's."""
    core, order, rounds, _, _, counts = plain
    core, order, rounds = np.asarray(core), np.asarray(order), int(rounds)
    n_r = problem.n_r
    max_gens = 3 * n_r + 4
    links = jax.jit(round_links)
    fix = jax.jit(link_fixpoint, static_argnames="max_gens")
    parent = jnp.arange(n_r, dtype=jnp.int32)
    L = jnp.full((n_r,), -1, jnp.int32)
    last = jnp.full((problem.n_s,), -1, jnp.int32)
    gens_total = live_total = rounds_with_links = 0
    for t in range(rounds):
        la, lb, lv, last = links(problem.inc_rid, jnp.asarray(order == t),
                                 last)
        core_t = jnp.asarray(np.where((order >= 0) & (order <= t), core,
                                      -1))
        n_links = int(jnp.sum(lv))
        _, _, gens1, live1 = fix(parent, L, core_t, la, lb, lv, max_gens=1)
        assert int(live1) == n_links, t
        parent, L, gens, live = fix(parent, L, core_t, la, lb, lv,
                                    max_gens=max_gens)
        assert (int(gens) >= 1) == (n_links > 0), t
        rounds_with_links += n_links > 0
        gens_total += int(gens)
        live_total += int(live)
    assert (gens_total, live_total) == (counts["fixpoint_gens"],
                                        counts["live_links"])
    assert rounds_with_links > 0
    assert counts["fixpoint_gens"] >= rounds_with_links
    np.testing.assert_array_equal(np.asarray(parent), np.asarray(plain[3]))


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
