"""Parity of the compiled peel engine against every other formulation.

The unified engine (repro.core.engine) must be *bit-identical* to the eager
work-efficient gather backend — same cores, same trace (order_round), same
round count — because both are driven by the one PeelSchedule; and the trace
replay must reproduce the callback-era interleaved hierarchy (join levels are
the canonical comparison metric, matching the two-phase ANH-TE tree which
the seed interleaved tests already pin down).
"""
from functools import partial

import numpy as np
import pytest

from repro.graph import generators
from repro.core import engine, kcore
from repro.core import (build_problem, replay_trace,
                        construct_tree_efficient, link_state_from_forest)
from repro.core.peel import exact_coreness, approx_coreness
from repro.core.hierarchy import build_hierarchy_levels
from repro.core.interleaved import build_hierarchy_interleaved, _resolve
from repro.core.nh_baseline import nh_coreness

GRAPHS = {
    "er30": generators.erdos_renyi(30, 0.25, seed=2),
    "planted": generators.planted_cliques(40, [8, 6, 5], 0.05, seed=3),
    "ba60": generators.barabasi_albert(60, 4, seed=4),
    "fig1": generators.paper_figure1_like(),
}
RS = [(1, 2), (2, 3), (2, 4)]


def problems():
    for gname in GRAPHS:
        for (r, s) in RS:
            yield pytest.param(gname, r, s, id=f"{gname}-r{r}s{s}")


def _sample_pairs(n_r, seed, k=60):
    rng = np.random.default_rng(seed)
    if n_r < 2:
        return np.zeros((0, 2), np.int64)
    return np.stack([rng.integers(0, n_r, k), rng.integers(0, n_r, k)], 1)


@pytest.mark.parametrize("gname,r,s", problems())
def test_engine_exact_matches_gather_and_oracle(gname, r, s):
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    eng = exact_coreness(p, backend="dense")
    gat = exact_coreness(p, backend="gather")
    oracle, _ = nh_coreness(p)
    np.testing.assert_array_equal(np.asarray(eng.core), oracle)
    np.testing.assert_array_equal(np.asarray(eng.core), np.asarray(gat.core))
    # the trace is part of the contract: identical schedules -> identical
    # peel rounds, so the hierarchy replay sees the same round stream
    np.testing.assert_array_equal(np.asarray(eng.order_round),
                                  np.asarray(gat.order_round))
    assert eng.rounds == gat.rounds


@pytest.mark.parametrize("delta", [0.1, 0.5, 1.0])
@pytest.mark.parametrize("gname,r,s", problems())
def test_engine_approx_matches_gather_and_bounds(gname, r, s, delta):
    from math import comb
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    eng = approx_coreness(p, delta=delta, backend="dense")
    gat = approx_coreness(p, delta=delta, backend="gather")
    np.testing.assert_array_equal(np.asarray(eng.core), np.asarray(gat.core))
    np.testing.assert_array_equal(np.asarray(eng.peel_value),
                                  np.asarray(gat.peel_value))
    np.testing.assert_array_equal(np.asarray(eng.order_round),
                                  np.asarray(gat.order_round))
    exact = np.asarray(exact_coreness(p).core)
    a = np.asarray(eng.core)
    factor = (comb(s, r) + delta) * (1 + delta)
    assert (a >= exact).all()
    assert (a <= np.maximum(np.ceil(factor * exact), exact)).all()


@pytest.mark.parametrize("gname,r,s", problems())
def test_pallas_scatter_matches_xla_fallback(gname, r, s):
    """The Pallas sorted-segment-sum decrement (interpret mode on CPU) must
    agree with the .at[].add oracle over the full peel."""
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    ref = exact_coreness(p, backend="dense", use_pallas=False)
    pal = exact_coreness(p, backend="dense", use_pallas=True)
    np.testing.assert_array_equal(np.asarray(ref.core), np.asarray(pal.core))
    np.testing.assert_array_equal(np.asarray(ref.order_round),
                                  np.asarray(pal.order_round))


@pytest.mark.parametrize("backend", ["gather", "dense"])
@pytest.mark.parametrize("gname,r,s", problems())
def test_trace_replay_hierarchy_matches_two_phase(gname, r, s, backend):
    """Trace-replay ANH-EL == callback-era join levels.  The seed pinned the
    callback-era tree to the two-phase ANH-TE tree, so TE join levels are the
    callback-era reference."""
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    res = build_hierarchy_interleaved(p, mode="exact", backend=backend)
    core = exact_coreness(p).core
    np.testing.assert_array_equal(np.asarray(res.core), np.asarray(core))
    t_te = build_hierarchy_levels(p, core)
    pairs = _sample_pairs(p.n_r, seed=7)
    np.testing.assert_array_equal(res.tree.join_levels(pairs),
                                  t_te.join_levels(pairs))


@pytest.mark.parametrize("gname,r,s", problems())
def test_trace_replay_equals_direct_replay(gname, r, s):
    """replay_trace over the dense-engine trace and over the gather trace
    build identical LINK states (same uf partition, same join levels)."""
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    st_e = replay_trace(p, exact_coreness(p, backend="dense"))
    st_g = replay_trace(p, exact_coreness(p, backend="gather"))
    t_e = construct_tree_efficient(p, st_e)
    t_g = construct_tree_efficient(p, st_g)
    pairs = _sample_pairs(p.n_r, seed=11)
    np.testing.assert_array_equal(t_e.join_levels(pairs),
                                  t_g.join_levels(pairs))


def forest_cases():
    """Every problem at the fixpoint's own width, plus the (2,3) problems
    and the k-core lane (ba60, r1s2) with the worklist forced to 8 slots,
    so that pending links refill it and handoffs are compacted."""
    for param in problems():
        yield pytest.param(*param.values, None, id=param.id)
    for gname in ("er30", "planted", "ba60", "fig1"):
        yield pytest.param(gname, 2, 3, 8, id=f"{gname}-r2s3-w8")
    yield pytest.param("ba60", 1, 2, 8, id="ba60-r1s2-w8")


@pytest.mark.parametrize("mode", ["exact", "approx"])
@pytest.mark.parametrize("gname,r,s,width", forest_cases())
def test_fused_forest_matches_replay_oracle(gname, r, s, width, mode,
                                            monkeypatch):
    """The on-device LINK fixpoint (hierarchy=True: uf/L threaded through
    the compiled peel carry) must reproduce the host trace-replay state
    EXACTLY — same resolved parents, same L at every root, same tree —
    whatever the worklist's width."""
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    if width is not None:
        monkeypatch.setattr(engine, "_dense_engine", partial(
            engine._dense_engine, link_width=width))
        monkeypatch.setattr(kcore, "_kcore_engine", partial(
            kcore._kcore_engine, link_width=width))
    peel = (exact_coreness if mode == "exact"
            else lambda q, **kw: approx_coreness(q, delta=0.1, **kw))
    res = peel(p, backend="dense", hierarchy=True)
    assert res.has_hierarchy
    state = replay_trace(p, res)
    ref_parent = _resolve(state.parent, np.arange(p.n_r, dtype=np.int64))
    got_parent = np.asarray(res.uf_parent).astype(np.int64)
    np.testing.assert_array_equal(got_parent, ref_parent)
    roots = np.unique(ref_parent)
    np.testing.assert_array_equal(
        np.asarray(res.uf_L).astype(np.int64)[roots], state.L[roots])
    t_fused = construct_tree_efficient(p, link_state_from_forest(
        res.peel_value, res.uf_parent, res.uf_L))
    t_replay = construct_tree_efficient(p, state)
    pairs = _sample_pairs(p.n_r, seed=13)
    np.testing.assert_array_equal(t_fused.join_levels(pairs),
                                  t_replay.join_levels(pairs))


@pytest.mark.parametrize("gname,r,s", problems())
def test_fused_hierarchy_does_not_perturb_coreness(gname, r, s):
    """hierarchy=True only extends the carry: core/order/rounds unchanged."""
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    plain = exact_coreness(p, backend="dense")
    fused = exact_coreness(p, backend="dense", hierarchy=True)
    np.testing.assert_array_equal(np.asarray(plain.core),
                                  np.asarray(fused.core))
    np.testing.assert_array_equal(np.asarray(plain.order_round),
                                  np.asarray(fused.order_round))
    assert plain.rounds == fused.rounds


def test_engine_empty_problem():
    """A graph with no s-cliques: engine returns deg0 (all zero) cores."""
    g = generators.tiny_named("path4")
    p = build_problem(g, 2, 4)  # path has no K4s
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    res = exact_coreness(p, backend="dense")
    np.testing.assert_array_equal(np.asarray(res.core),
                                  np.zeros(p.n_r, np.int64))


# ---------------------------------------------------------------------------
# Pallas round bodies (the scatter-only plan, the default, and the round
# megakernel, interpret mode only): full-peel bit-identity incl. the fused
# hierarchy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", ["scatter", "megakernel"])
@pytest.mark.parametrize("mode", ["exact", "approx"])
@pytest.mark.parametrize("gname,r,s", problems())
def test_megakernel_full_peel_bit_identical(gname, r, s, mode, kernel):
    """Each Pallas round body must reproduce the multi-op XLA round body
    bit-for-bit across the whole peel — cores, trace, rounds AND the
    fused LINK forest (the forest consumes the per-round a_mask, so it
    would catch a divergence the final cores might mask)."""
    from repro.core.engine import dense_coreness, make_schedule
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    sched = make_schedule(p, mode, 0.1)
    ref = dense_coreness(p, sched, use_pallas=False, hierarchy=True)
    got = dense_coreness(p, sched, use_pallas=True, hierarchy=True,
                         interpret=True,
                         fused_kernel=kernel == "megakernel")
    names = ("core", "order_round", "rounds", "fixpoint_counts",
             "uf_parent", "uf_L")
    assert len(ref) == len(got) == len(names)
    for f, a, b in zip(names, ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f)


def test_compiled_megakernel_is_refused():
    """The megakernel's in-kernel 1-D gather cannot lower for a TPU, so a
    compiled (non-interpret) megakernel must raise before any compile."""
    from repro.core import ConfigError
    from repro.core.engine import dense_coreness, make_schedule
    p = build_problem(GRAPHS["planted"], 2, 3)
    with pytest.raises(ConfigError, match="2D gather"):
        dense_coreness(p, make_schedule(p, "exact"), use_pallas=True,
                       interpret=False, fused_kernel=True)


# ---------------------------------------------------------------------------
# k-core fast lane (r1s2): bit-identity against the generic engine
# ---------------------------------------------------------------------------

KCORE_GRAPHS = list(GRAPHS) + ["er80"]
GRAPHS["er80"] = generators.erdos_renyi(80, 0.1, seed=9)


@pytest.mark.parametrize("mode", ["exact", "approx"])
@pytest.mark.parametrize("gname", KCORE_GRAPHS)
def test_kcore_lane_bit_identical(gname, mode):
    """The r1s2 vertex-degree lane (one-shot edge-list fixpoint) must be
    bit-identical to the generic incidence engine: same cores, same trace,
    same rounds, same resolved forest."""
    p = build_problem(GRAPHS[gname], 1, 2)
    peel = (exact_coreness if mode == "exact"
            else lambda q, **kw: approx_coreness(q, delta=0.1, **kw))
    ref = peel(p, backend="dense", use_pallas=False, hierarchy=True,
               fast_lane=False)
    kc = peel(p, backend="dense", hierarchy=True, fast_lane=True)
    for f in ("core", "order_round", "uf_parent", "uf_L"):
        np.testing.assert_array_equal(np.asarray(getattr(ref, f)),
                                      np.asarray(getattr(kc, f)),
                                      err_msg=f)
    assert ref.rounds == kc.rounds


def test_kcore_lane_is_the_r1s2_default():
    """peel._run routes (1,2) dense peels to the k-core lane unless the
    caller pins the Pallas round body."""
    from repro.core import peel as peel_mod
    calls = []
    orig = peel_mod.kcore_coreness

    def spy(problem, schedule, **kw):
        calls.append(kw)
        return orig(problem, schedule, **kw)

    p = build_problem(GRAPHS["er30"], 1, 2)
    try:
        peel_mod.kcore_coreness = spy
        exact_coreness(p, backend="dense")
        assert len(calls) == 1          # default: lane taken
        exact_coreness(p, backend="dense", use_pallas=True)
        assert len(calls) == 1          # pinned Pallas: lane skipped
    finally:
        peel_mod.kcore_coreness = orig


def test_kcore_lane_matches_replay_oracle():
    """The one-shot edge-list fixpoint forest == host trace replay (the
    confluence argument, end-to-end)."""
    p = build_problem(GRAPHS["ba60"], 1, 2)
    res = exact_coreness(p, backend="dense", hierarchy=True, fast_lane=True)
    state = replay_trace(p, res)
    ref_parent = _resolve(state.parent, np.arange(p.n_r, dtype=np.int64))
    np.testing.assert_array_equal(
        np.asarray(res.uf_parent).astype(np.int64), ref_parent)
    t_fused = construct_tree_efficient(p, link_state_from_forest(
        res.peel_value, res.uf_parent, res.uf_L))
    t_replay = construct_tree_efficient(p, state)
    pairs = _sample_pairs(p.n_r, seed=17)
    np.testing.assert_array_equal(t_fused.join_levels(pairs),
                                  t_replay.join_levels(pairs))
