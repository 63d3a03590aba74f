"""Correctness of the paper's algorithms against definition-level oracles."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.graph import generators
# oracle/parity tests import the building blocks from their submodules —
# the deprecated package-level names are exercised (once) by test_facade.py
from repro.core import build_problem, same_partition, edge_density
from repro.core.peel import exact_coreness, approx_coreness
from repro.core.hierarchy import (build_hierarchy_levels,
                                  build_hierarchy_basic)
from repro.core.interleaved import build_hierarchy_interleaved
from repro.core.nh_baseline import (nh_coreness, nh_hierarchy,
                                    brute_force_coreness)
from repro.core.nuclei import cut_hierarchy, nuclei_without_hierarchy

GRAPHS = {
    "triangle": generators.tiny_named("triangle"),
    "k4": generators.tiny_named("k4"),
    "path4": generators.tiny_named("path4"),
    "two_triangles": generators.tiny_named("two_triangles"),
    "bowtie_plus": generators.tiny_named("bowtie_plus"),
    "fig1": generators.paper_figure1_like(),
    "er20": generators.erdos_renyi(20, 0.35, seed=1),
    "er30": generators.erdos_renyi(30, 0.25, seed=2),
    "planted": generators.planted_cliques(40, [8, 6, 5], 0.05, seed=3),
    "ba60": generators.barabasi_albert(60, 4, seed=4),
}
RS = [(1, 2), (2, 3), (1, 3), (3, 4), (2, 4)]


def problems():
    for gname, g in GRAPHS.items():
        for (r, s) in RS:
            yield pytest.param(gname, r, s, id=f"{gname}-r{r}s{s}")


@pytest.mark.parametrize("gname,r,s", problems())
def test_exact_coreness_matches_oracles(gname, r, s):
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    got = np.asarray(exact_coreness(p).core)
    seq, _ = nh_coreness(p)
    np.testing.assert_array_equal(got, seq)
    bf = brute_force_coreness(p)
    np.testing.assert_array_equal(got, bf)


@pytest.mark.parametrize("gname,r,s", problems())
def test_exact_coreness_dense_backend(gname, r, s):
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    g = np.asarray(exact_coreness(p, backend="gather").core)
    d = np.asarray(exact_coreness(p, backend="dense").core)
    np.testing.assert_array_equal(g, d)


@pytest.mark.parametrize("delta", [0.1, 0.5, 1.0])
@pytest.mark.parametrize("gname,r,s", problems())
def test_approx_coreness_bounds(gname, r, s, delta):
    from math import comb
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    exact = np.asarray(exact_coreness(p).core)
    approx = np.asarray(approx_coreness(p, delta=delta).core)
    C = comb(s, r)
    factor = (C + delta) * (1 + delta)
    assert (approx >= exact).all(), "estimate must be >= true core"
    ok = approx <= np.maximum(np.ceil(factor * exact), exact)
    assert ok.all(), (approx[~ok], exact[~ok], factor)


def _sample_pairs(n_r, rng, k=60):
    if n_r < 2:
        return np.zeros((0, 2), np.int64)
    a = rng.integers(0, n_r, size=k)
    b = rng.integers(0, n_r, size=k)
    return np.stack([a, b], axis=1)


@pytest.mark.parametrize("gname,r,s", problems())
def test_hierarchy_te_matches_nh(gname, r, s):
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    core = exact_coreness(p).core
    t_te = build_hierarchy_levels(p, core)
    t_nh = nh_hierarchy(p, np.asarray(core))
    rng = np.random.default_rng(0)
    pairs = _sample_pairs(p.n_r, rng)
    np.testing.assert_array_equal(t_te.join_levels(pairs),
                                  t_nh.join_levels(pairs))


@pytest.mark.parametrize("gname,r,s", problems())
def test_hierarchy_bl_matches_te(gname, r, s):
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    core = exact_coreness(p).core
    t_te = build_hierarchy_levels(p, core)
    t_bl = build_hierarchy_basic(p, core)
    rng = np.random.default_rng(1)
    pairs = _sample_pairs(p.n_r, rng)
    np.testing.assert_array_equal(t_te.join_levels(pairs),
                                  t_bl.join_levels(pairs))


@pytest.mark.parametrize("gname,r,s", problems())
def test_hierarchy_el_interleaved_matches_te(gname, r, s):
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    res = build_hierarchy_interleaved(p, mode="exact")
    core = exact_coreness(p).core
    np.testing.assert_array_equal(np.asarray(res.core), np.asarray(core))
    t_te = build_hierarchy_levels(p, core)
    rng = np.random.default_rng(2)
    pairs = _sample_pairs(p.n_r, rng)
    np.testing.assert_array_equal(res.tree.join_levels(pairs),
                                  t_te.join_levels(pairs))


@pytest.mark.parametrize("gname,r,s", problems())
def test_chain_reduction_equivalent_to_all_pairs(gname, r, s):
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    core = exact_coreness(p).core
    t_chain = build_hierarchy_levels(p, core, chain=True)
    t_full = build_hierarchy_levels(p, core, chain=False)
    rng = np.random.default_rng(3)
    pairs = _sample_pairs(p.n_r, rng)
    np.testing.assert_array_equal(t_chain.join_levels(pairs),
                                  t_full.join_levels(pairs))


@pytest.mark.parametrize("gname", ["fig1", "planted", "er20"])
@pytest.mark.parametrize("r,s", [(1, 2), (2, 3), (1, 3)])
def test_cut_hierarchy_matches_connectivity(gname, r, s):
    p = build_problem(GRAPHS[gname], r, s)
    if p.n_r == 0:
        pytest.skip("no r-cliques")
    core = exact_coreness(p).core
    tree = build_hierarchy_levels(p, core)
    kmax = int(np.asarray(core).max())
    for c in range(1, kmax + 1):
        via_tree = cut_hierarchy(tree, c)
        via_cc = nuclei_without_hierarchy(p, core, c)
        assert same_partition(via_tree, via_cc), f"c={c}"


def test_k_core_special_case():
    """(1,2) nucleus == classic k-core; verify against a hand example."""
    g = generators.tiny_named("bowtie_plus")
    p = build_problem(g, 1, 2)
    core = np.asarray(exact_coreness(p).core)
    # two K4s joined by one edge: every vertex has k-core number 3
    np.testing.assert_array_equal(core, np.full(8, 3))


def test_k_truss_special_case():
    """(2,3) nucleus: triangle counts per edge in a K4 are 2."""
    g = generators.tiny_named("k4")
    p = build_problem(g, 2, 3)
    core = np.asarray(exact_coreness(p).core)
    np.testing.assert_array_equal(core, np.full(6, 2))


def _edge_density_bruteforce(g_edges, vertices):
    """Definition-level oracle: the per-edge Python set scan the vectorized
    ``edge_density`` replaced."""
    k = len(vertices)
    if k < 2:
        return 0.0
    vs = set(int(x) for x in vertices)
    inside = sum(1 for u, v in g_edges if int(u) in vs and int(v) in vs)
    return inside / (k * (k - 1) / 2)


def test_kronecker_generator_is_seeded_and_simple():
    """Graph500 Kronecker: 2**scale vertices, deterministic per seed, no
    self-loops, canonical (u < v) unique edges."""
    a = generators.kronecker(8, edge_factor=16, seed=3)
    b = generators.kronecker(8, edge_factor=16, seed=3)
    c = generators.kronecker(8, edge_factor=16, seed=4)
    assert a.n == 256
    ea, eb = np.asarray(a.edges), np.asarray(b.edges)
    np.testing.assert_array_equal(ea, eb)
    assert not np.array_equal(ea, np.asarray(c.edges))
    assert np.all(ea[:, 0] < ea[:, 1])
    assert len({tuple(e) for e in ea.tolist()}) == a.m
    assert 0 < a.m <= 16 * 256


def test_edge_density_matches_bruteforce():
    rng = np.random.default_rng(7)
    g = generators.erdos_renyi(30, 0.2, seed=9)
    edges = np.asarray(g.edges)
    for k in [0, 1, 2, 5, 13, 30]:
        for trial in range(4):
            vs = rng.choice(30, size=k, replace=False)
            got = edge_density(edges, vs)
            want = _edge_density_bruteforce(edges, vs)
            assert got == pytest.approx(want), (k, trial)
    # empty edge array
    assert edge_density(np.zeros((0, 2), np.int64), np.arange(5)) == 0.0


def test_fig1_like_hierarchy_structure():
    """The fig1-like graph must produce a nested multi-level hierarchy."""
    g = generators.paper_figure1_like()
    p = build_problem(g, 1, 3)
    core = exact_coreness(p).core
    tree = build_hierarchy_levels(p, core)
    assert tree.n_internal >= 2, "expected nested structure"
    lv = tree.level[tree.n_leaves:]
    assert (np.diff(np.sort(lv)) >= 0).all()
    # roots exist and levels of internal nodes are valid core values
    assert (tree.parent == -1).sum() >= 1
