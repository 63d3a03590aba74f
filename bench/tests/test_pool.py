"""A run's graphs and the order of the window's jobs are functions of
``--seed``: the same seed gives the same graphs; another seed permutes
the window graphs' vertex ids anew (the same sizes and rounds) and draws
another fresh graph."""
import itertools

import numpy as np

import reference
from generators import kronecker
from graphs import canonical_edges, relabel, rng_for
from loops.jobs import FRESH_STREAM, job_order

PARAMS = {"generator": "kronecker", "scale": 7, "edge_factor": 16,
          "initiator": [0.57, 0.19, 0.19, 0.05]}
BIG_SEED = 2**31 + 12345  # seeds may run past 32 signed bits


def graphs(seed, size=3, base_seed=0):
    """The window graphs and the fresh graph, as the jobs loop draws
    them."""
    out = []
    for i in range(size):
        n, base = kronecker.graph(PARAMS, rng_for(base_seed, i))
        out.append(relabel(base, rng_for(seed, i).permutation(n)))
    out.append(kronecker.graph(PARAMS, rng_for(seed, FRESH_STREAM))[1])
    return out


def test_same_seed_same_graphs():
    for a, b in zip(graphs(BIG_SEED), graphs(BIG_SEED)):
        assert np.array_equal(a, b)


def test_graphs_differ_within_a_run_and_between_seeds():
    g = graphs(BIG_SEED)
    assert not np.array_equal(g[0], g[1])
    other = graphs(BIG_SEED + 1)
    assert not any(np.array_equal(a, b) for a, b in zip(g, other))


def test_seeds_permute_the_same_window_graphs():
    """Another seed gives each window graph other edges but the same
    sizes, triangles and peel rounds."""
    a, b = graphs(BIG_SEED)[0], graphs(BIG_SEED + 1)[0]
    assert a.shape == b.shape and not np.array_equal(a, b)
    ia, ib = reference.incidence(128, a), reference.incidence(128, b)
    assert ia.n_s == ib.n_s
    assert reference.peel(ia)[1] == reference.peel(ib)[1]
    assert sorted(reference.peel(ia)[0]) == sorted(reference.peel(ib)[0])


def test_graph_has_the_configured_size():
    n, e = kronecker.graph(PARAMS, rng_for(3, 0))
    assert n == 128
    assert 0 < e.shape[0] <= 16 * 128
    assert e.max() < n


def test_job_order_repeats_for_a_seed():
    a = list(itertools.islice(job_order(BIG_SEED, 4), 12))
    b = list(itertools.islice(job_order(BIG_SEED, 4), 12))
    assert a == b
    # every cycle of four holds each graph once
    for k in range(0, 12, 4):
        assert sorted(a[k:k + 4]) == [0, 1, 2, 3]


def test_job_order_differs_between_seeds():
    orders = {tuple(itertools.islice(job_order(s, 4), 12))
              for s in range(10)}
    assert len(orders) > 1


def test_negative_seed_is_accepted():
    assert len(list(itertools.islice(job_order(-3, 4), 4))) == 4


def test_edges_are_canonical():
    e = graphs(5, size=1)[0]
    assert (e[:, 0] < e[:, 1]).all()
    order = np.lexsort((e[:, 1], e[:, 0]))
    assert np.array_equal(order, np.arange(e.shape[0]))
    assert np.unique(e, axis=0).shape == e.shape


def test_program_canonicalizes_edges_alike():
    """The harness hands the program a ``Graph`` of its own canonical
    edges; ``repro.graph.make_graph`` gives the same edges."""
    from repro.graph import make_graph
    raw = kronecker.kronecker_edges(6, 16, PARAMS["initiator"],
                                    rng_for(1, 0))
    assert np.array_equal(np.asarray(make_graph(64, raw).edges),
                          canonical_edges(raw))


def test_reference_on_a_hand_counted_graph():
    # K4 on 0..3 plus a pendant edge 3-4: 6 + 1 edges, 4 triangles;
    # every K4 edge lies in 2 triangles, the pendant edge in none
    edges = canonical_edges(np.array(
        [[0, 1], [0, 2], [0, 3], [1, 2], [1, 3], [2, 3], [4, 3]]))
    inc = reference.incidence(5, edges)
    assert inc.n_r == 7 and inc.n_s == 4
    assert inc.deg0.tolist() == [2, 2, 2, 2, 2, 2, 0]
    core, rounds = reference.peel(inc)
    assert core.tolist() == [2, 2, 2, 2, 2, 2, 0]
    assert rounds == 2
    assert reference.partition(inc, core, 2).tolist() == [0] * 6 + [-1]
