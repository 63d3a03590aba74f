"""The warm ``Session`` front door (DESIGN.md §8).

Contracts:
  * PARITY — ``Session(cfg).decompose(p)`` is array-for-array identical to
    ``decompose(p, cfg)`` (core, rounds, trace, forest, tree) on every
    golden fixture, for exact and approximate peels: the shape padding
    (ghost s-rows + pre-peeled ghost r-cliques) and the schedule
    canonicalization are behaviour-invisible.
  * BUCKETS — similar-but-distinct shapes land in one shape class
    (``stats`` shows warm hits), the padding helpers hit the documented
    boundaries, and canonicalized schedules preserve the approx round cap.
  * FALLBACK — configs that resolve off the dense engine still work (and
    are counted as fallbacks), including ``backend='auto'``.
"""
import json

import jax
import numpy as np
import pytest

from repro.core import (GraphDelta, NucleusConfig, Session, build_problem,
                        decompose)
from repro.core.schedule import PeelSchedule
from repro.core.session import bucket_size, canonical_schedule
from repro.graph import generators, make_graph
from repro.graph.generators import golden_suite

pytestmark = pytest.mark.fast

GRAPHS = golden_suite()


def _assert_same(dec_s, dec_c, label):
    np.testing.assert_array_equal(dec_s.core, dec_c.core,
                                  err_msg=f"{label}: core")
    assert dec_s.rounds == dec_c.rounds, label
    assert type(dec_s.rounds) is int, label
    np.testing.assert_array_equal(dec_s.order_round, dec_c.order_round,
                                  err_msg=f"{label}: order_round")
    np.testing.assert_array_equal(dec_s.peel_value, dec_c.peel_value,
                                  err_msg=f"{label}: peel_value")
    if dec_c.has_hierarchy:
        np.testing.assert_array_equal(np.asarray(dec_s.tree.parent),
                                      np.asarray(dec_c.tree.parent),
                                      err_msg=f"{label}: tree parent")
        np.testing.assert_array_equal(np.asarray(dec_s.tree.level),
                                      np.asarray(dec_c.tree.level),
                                      err_msg=f"{label}: tree level")


# ---------------------------------------------------------------------------
# Padding + canonicalization helpers
# ---------------------------------------------------------------------------

def test_bucket_size_boundaries():
    assert bucket_size(0) == 64
    assert bucket_size(1) == 64
    assert bucket_size(64) == 64
    assert bucket_size(65) == 128
    assert bucket_size(128) == 128
    assert bucket_size(129) == 256
    assert bucket_size(3, floor=2) == 4


def test_canonical_schedule_exact_ignores_graph_size():
    a = canonical_schedule("exact", 3, 0.1, 10)
    b = canonical_schedule("exact", 3, 0.5, 10_000)
    assert a == b  # one static jit key for the whole exact class


def test_canonical_schedule_approx_preserves_cap():
    for n in (2, 10, 100, 1_000, 50_000):
        for delta in (0.1, 0.5):
            full = PeelSchedule(kind="approx", s_choose_r=3, delta=delta,
                                n=n)
            canon = canonical_schedule("approx", 3, delta, n)
            assert canon.cap() == full.cap(), (n, delta)
            assert canon.n <= n or n < 2
            if canon.n > 2:  # minimality: one less vertex drops the cap
                smaller = PeelSchedule(kind="approx", s_choose_r=3,
                                       delta=delta, n=canon.n - 1)
                assert smaller.cap() < full.cap(), (n, delta)


def test_same_cap_graphs_share_a_bucket():
    # delta is deliberately coarse: at delta=1.5 the approx round cap is
    # flat across nearby vertex counts, so canonicalization collapses the
    # two schedules onto one static key (at tiny delta the cap — and hence
    # the bucket — legitimately moves with nearly every n)
    cfg = NucleusConfig(r=2, s=3, method="approx", delta=1.5,
                        backend="dense", hierarchy="none")
    sess = Session(cfg)
    p1 = build_problem(generators.planted_cliques(40, [8, 6], 0.05, seed=1),
                       2, 3)
    p2 = build_problem(generators.planted_cliques(41, [8, 6], 0.05, seed=2),
                       2, 3)
    k1, k2 = sess.bucket_key(p1), sess.bucket_key(p2)
    # distinct graph sizes, same schedule class + shape class
    assert p1.g.n != p2.g.n
    assert k1 == k2


# ---------------------------------------------------------------------------
# Parity vs decompose()
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_session_parity_exact_fused(gname):
    problem = build_problem(GRAPHS[gname](), 2, 3)
    if problem.n_r == 0:
        pytest.skip("no r-cliques")
    cfg = NucleusConfig(r=2, s=3, backend="dense", hierarchy="fused")
    _assert_same(Session(cfg).decompose(problem), decompose(problem, cfg),
                 gname)


@pytest.mark.parametrize("gname", ["two_triangles", "planted40", "er20"])
def test_session_parity_approx(gname):
    problem = build_problem(GRAPHS[gname](), 2, 3)
    cfg = NucleusConfig(r=2, s=3, method="approx", delta=0.25,
                        backend="dense", hierarchy="fused")
    _assert_same(Session(cfg).decompose(problem), decompose(problem, cfg),
                 gname)


@pytest.mark.slow
@pytest.mark.parametrize("r,s", [(1, 2), (3, 4)])
def test_session_parity_other_rs(r, s):
    problem = build_problem(GRAPHS["planted40"](), r, s)
    if problem.n_r == 0:
        pytest.skip("no r-cliques")
    cfg = NucleusConfig(r=r, s=s, backend="dense", hierarchy="fused")
    _assert_same(Session(cfg).decompose(problem), decompose(problem, cfg),
                 f"r{r}s{s}")


def test_session_accepts_graphs_and_builds_problems():
    g = generators.planted_cliques(90, [9, 7], 0.04, seed=5)
    cfg = NucleusConfig(r=2, s=3, backend="dense", hierarchy="none")
    _assert_same(Session(cfg).decompose(g), decompose(g, cfg), "from-graph")


# ---------------------------------------------------------------------------
# Bucketing + stats
# ---------------------------------------------------------------------------

def test_same_bucket_stream_is_warm():
    cfg = NucleusConfig(r=2, s=3, backend="dense", hierarchy="fused")
    sess = Session(cfg)
    graphs = [generators.planted_cliques(100 + 3 * i, [10, 8], 0.03,
                                         seed=20 + i) for i in range(4)]
    problems = [build_problem(g, 2, 3) for g in graphs]
    shapes = {(p.n_r, p.n_s) for p in problems}
    assert len(shapes) > 1, "stream must have distinct shapes"
    decs = sess.decompose_many(problems)
    assert len(decs) == 4
    assert len(sess.stats["buckets"]) == 1, sess.stats
    assert sess.stats["cold"] == 1 and sess.stats["warm"] == 3, sess.stats
    for p, d in zip(problems, decs):
        _assert_same(d, decompose(p, cfg), f"n_r={p.n_r}")


def test_fallback_backends_still_work():
    problem = build_problem(GRAPHS["two_triangles"](), 2, 3)
    for backend, hierarchy in [("gather", "replay"), ("nh", "two_phase")]:
        cfg = NucleusConfig(r=2, s=3, backend=backend, hierarchy=hierarchy)
        sess = Session(cfg)
        dec = sess.decompose(problem)
        assert sess.stats["fallback"] == 1
        np.testing.assert_array_equal(dec.core,
                                      decompose(problem, cfg).core)


def test_use_pallas_rides_the_warm_path():
    """The Pallas scatter plan is bucketed like everything else: a stream of
    same-bucket problems with use_pallas=True shares ONE executable (cold
    once, warm after, no fallback) and stays array-identical to the
    unpadded decompose()."""
    cfg = NucleusConfig(r=2, s=3, backend="dense", hierarchy="fused",
                        use_pallas=True)
    sess = Session(cfg)
    graphs = [generators.planted_cliques(100 + 3 * i, [10, 8], 0.03,
                                         seed=20 + i) for i in range(3)]
    problems = [build_problem(g, 2, 3) for g in graphs]
    decs = sess.decompose_many(problems)
    assert sess.stats["fallback"] == 0, sess.stats
    assert len(sess.stats["buckets"]) == 1, sess.stats
    assert sess.stats["cold"] == 1 and sess.stats["warm"] == 2, sess.stats
    for p, d in zip(problems, decs):
        _assert_same(d, decompose(p, cfg), f"pallas-warm n_r={p.n_r}")


def test_pallas_over_budget_plan_falls_back():
    """A padded Pallas plan bigger than the plan budget must take the
    cold path, not die: the Session races plan bytes against
    PLAN_BUDGET_BYTES before bucketing."""
    from repro.core import session as session_mod
    problem = build_problem(GRAPHS["planted40"](), 2, 3)
    cfg = NucleusConfig(r=2, s=3, backend="dense", hierarchy="fused",
                        use_pallas=True)
    old = session_mod.PLAN_BUDGET_BYTES
    try:
        session_mod.PLAN_BUDGET_BYTES = 1  # force over-budget
        sess = Session(cfg)
        dec = sess.decompose(problem)
        assert sess.stats["fallback"] == 1
        _assert_same(dec, decompose(problem, cfg), "pallas-over-budget")
    finally:
        session_mod.PLAN_BUDGET_BYTES = old


def test_fallback_preserves_auto_plan_provenance():
    """The fallback path executes the already-planned config — the
    serialized plan must still say 'auto' was requested, with the
    planner's real reasons (not 'explicitly configured')."""
    tiny = build_problem(GRAPHS["two_triangles"](), 2, 3)  # n_r < TINY_NR
    sess = Session(NucleusConfig(r=2, s=3, backend="auto",
                                 hierarchy="auto"))
    dec = sess.decompose(tiny)
    ref = decompose(tiny, NucleusConfig(r=2, s=3, backend="auto",
                                        hierarchy="auto"))
    assert sess.stats["fallback"] == 1  # tiny-on-cpu resolves off dense
    assert dec.plan == ref.plan
    assert dec.plan.was_auto
    assert dec.plan.requested_backend == "auto"
    assert "explicitly configured" not in dec.plan_report()


def test_session_resolves_auto_per_problem():
    big = generators.planted_cliques(120, [10, 8], 0.03, seed=7)
    sess = Session(NucleusConfig(r=2, s=3, backend="auto",
                                 hierarchy="auto"))
    dec = sess.decompose(big)
    assert dec.plan is not None and dec.plan.was_auto
    assert dec.config.backend in ("dense", "gather")
    _assert_same(dec, decompose(big, NucleusConfig(
        r=2, s=3, backend=dec.config.backend,
        hierarchy=dec.config.hierarchy)), "auto-session")


# ---------------------------------------------------------------------------
# Plan-budget gate, shape-only keys, LRU, boundaries (the PR-7 fixes)
# ---------------------------------------------------------------------------

def test_plan_budget_gate_counts_padded_bytes():
    """Regression: the gate must race the PADDED plan footprint against
    the budget.  The old gate used unpadded sizes, so a problem whose
    pow2-padded member matrix landed over budget was still sent down the
    bucketed path.  A budget between the two sizes must fall back."""
    from repro.core import session as session_mod
    from repro.kernels.segment_sum import DEFAULT_CHUNK_E
    problem = build_problem(GRAPHS["planted40"](), 2, 3)
    C = problem.n_sub
    unpadded = 4 * problem.n_s * C * C
    padded = 4 * session_mod.bucket_size(problem.n_s * C,
                                         DEFAULT_CHUNK_E) * C
    assert padded == session_mod.padded_plan_bytes(problem)
    assert unpadded < padded, "fixture must straddle the pad boundary"
    cfg = NucleusConfig(r=2, s=3, backend="dense", hierarchy="fused",
                        use_pallas=True)
    old = session_mod.PLAN_BUDGET_BYTES
    try:
        session_mod.PLAN_BUDGET_BYTES = (unpadded + padded) // 2
        sess = Session(cfg)
        dec = sess.decompose(problem)
        assert sess.stats["fallback"] == 1, (
            "budget between unpadded and padded bytes must take the "
            "cold path")
        _assert_same(dec, decompose(problem, cfg), "padded-gate")
    finally:
        session_mod.PLAN_BUDGET_BYTES = old


def test_bucket_key_matches_plan_built_key():
    """The shape-derived ScatterSpec twin equals the spec of the real
    (array-materializing) plan — same bucket keys as the old path."""
    from repro.core import session as session_mod
    cfg = NucleusConfig(r=2, s=3, backend="dense", hierarchy="fused",
                        use_pallas=True)
    sess = Session(cfg)
    for gname in sorted(GRAPHS):
        problem = build_problem(GRAPHS[gname](), 2, 3)
        if problem.n_s == 0:
            continue
        key = sess.bucket_key(problem)
        n_r_pad = bucket_size(problem.n_r, sess.bucket_floor)
        real_spec = sess._pallas_plan(problem, n_r_pad)[2]
        # read the field by name: the key tuple grew a trailing `shards`
        # field for sharded shape classes (DESIGN.md §13)
        assert session_mod._Bucket(*key).pallas == real_spec, gname


def test_bucket_key_builds_no_plan_arrays(monkeypatch):
    """Probing a key must never materialize padded plan arrays."""
    cfg = NucleusConfig(r=2, s=3, backend="dense", hierarchy="fused",
                        use_pallas=True)
    sess = Session(cfg)
    problem = build_problem(GRAPHS["er20"](), 2, 3)

    def boom(*a, **k):
        raise AssertionError("bucket_key called _pallas_plan")

    monkeypatch.setattr(sess, "_pallas_plan", boom)
    key = sess.bucket_key(problem)
    assert key[-1] is not None  # pallas spec present, derived shape-only


def test_bucket_hit_lru_order():
    sess = Session(NucleusConfig(r=2, s=3), bucket_cap=2)
    assert sess._bucket_hit("a") is False
    assert sess._bucket_hit("b") is False
    assert sess._bucket_hit("a") is True    # refreshes a
    assert sess._bucket_hit("c") is False   # evicts b, the stalest
    assert set(sess.stats["buckets"]) == {"a", "c"}
    assert sess.stats["evictions"] == 1
    assert sess._bucket_hit("b") is False   # re-seen post-eviction: cold


def test_bucket_lru_eviction_bounds_stats():
    cfg = NucleusConfig(r=1, s=2, backend="dense", hierarchy="none")
    sess = Session(cfg, bucket_floor=1, bucket_cap=2)
    for gname in sorted(GRAPHS):
        sess.decompose(build_problem(GRAPHS[gname](), 1, 2))
    assert len(sess.stats["buckets"]) <= 2, sess.stats
    assert sess.stats["evictions"] > 0, sess.stats
    assert (sess.stats["cold"] + sess.stats["warm"]
            == sess.stats["decompositions"] - sess.stats["fallback"])


@pytest.mark.parametrize("n", [64, 65, 255, 256, 257])
def test_session_parity_at_bucket_boundaries(n):
    """Cycles sized to straddle both padding boundaries: n_r at the
    bucket floor (64) and just past it, and the megakernel edge axis at
    chunk_e (2*256 = 512) and one edge to either side."""
    edges = np.array([[i, (i + 1) % n] for i in range(n)])
    problem = build_problem(make_graph(n, edges), 1, 2)
    assert problem.n_r == n
    assert int(problem.mem_sids.shape[0]) == 2 * n
    cfg = NucleusConfig(r=1, s=2, backend="dense", hierarchy="fused",
                        use_pallas=True)
    _assert_same(Session(cfg).decompose(problem),
                 decompose(problem, cfg), f"cycle{n}")


def test_kcore_fast_lane_follows_pallas_default_profile(
        tmp_path, monkeypatch):
    """use_pallas=None routing is profile-driven: pallas_default=False
    sends r1s2 to the k-core fast lane, pallas_default=True pins the
    megakernel (no fast lane) — with identical results."""
    from repro.core import peel as peel_mod
    from repro.core import planner_profile
    problem = build_problem(GRAPHS["er20"](), 1, 2)
    calls = []
    real = peel_mod.kcore_coreness

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(peel_mod, "kcore_coreness", spy)

    def set_profile(flag):
        p = tmp_path / f"prof_{flag}.json"
        p.write_text(json.dumps({
            "format": "repro.planner-profile", "version": 1,
            "profiles": {jax.default_backend(): {"pallas_default": flag}}}))
        monkeypatch.setattr(planner_profile, "PROFILE_PATH", str(p))
        planner_profile.reset_cache()

    try:
        set_profile(False)
        r1 = peel_mod.exact_coreness(problem, backend="dense",
                                     use_pallas=None, hierarchy=True)
        assert calls, "pallas_default=False must route r1s2 to the lane"
        calls.clear()
        set_profile(True)
        r2 = peel_mod.exact_coreness(problem, backend="dense",
                                     use_pallas=None, hierarchy=True)
        assert not calls, "pallas_default=True pins the megakernel"
        np.testing.assert_array_equal(np.asarray(r1.core),
                                      np.asarray(r2.core))
        np.testing.assert_array_equal(np.asarray(r1.uf_parent),
                                      np.asarray(r2.uf_parent))
    finally:
        planner_profile.reset_cache()


# ---------------------------------------------------------------------------
# Session.update: the streaming warm path
# ---------------------------------------------------------------------------

def test_session_update_streams_warm():
    cfg = NucleusConfig(r=1, s=2, backend="dense", hierarchy="fused")
    sess = Session(cfg)
    g = GRAPHS["er20"]()
    dec = sess.decompose(build_problem(g, 1, 2))
    present = {tuple(r) for r in np.asarray(g.edges).tolist()}
    ins = next((u, v) for u in range(g.n) for v in range(u + 1, g.n)
               if (u, v) not in present)
    d2 = sess.update(dec, GraphDelta(insert=np.array([ins])))
    assert sess.stats["updates"] == 1
    assert sess.stats["stream_cold"] >= 1
    # the inverse edit lands in the same padded shape classes: warm
    d3 = sess.update(d2, GraphDelta(delete=np.array([ins])))
    assert sess.stats["updates"] == 2
    assert sess.stats["stream_warm"] >= 1, sess.stats
    fresh = decompose(build_problem(d3.problem.g, 1, 2), cfg)
    np.testing.assert_array_equal(np.asarray(d3.core),
                                  np.asarray(fresh.core))
    np.testing.assert_array_equal(np.asarray(d3.uf_parent),
                                  np.asarray(fresh.uf_parent))
    np.testing.assert_array_equal(np.asarray(d3.uf_L),
                                  np.asarray(fresh.uf_L))
