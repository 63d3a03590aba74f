"""Incremental ``update(delta)`` for live graphs (DESIGN.md §10).

Contracts:
  * PARITY — after any supported insert/delete sequence,
    ``Decomposition.update`` is array-for-array identical to a fresh
    ``decompose()`` of the edited graph: core, peel_value, the fused
    join forest, the hierarchy tree, and cut labels.  Holds for the
    r1s2 fast lane and for (2, 3) through the generic engine, under
    randomized op sequences.
  * DELTA — ``GraphDelta`` canonicalizes (u, v) order, rejects
    self-loops, and is strict about insert-present / delete-absent.
  * ERRORS — approx artifacts, unsupported (r, s), non-fused
    hierarchies, and problem-less (deserialized) artifacts fail with
    actionable messages instead of corrupting state.
"""
from functools import partial

import numpy as np
import pytest

from repro.core import GraphDelta, NucleusConfig, decompose, streaming
from repro.core.streaming import SUPPORTED_RS
from repro.graph import make_graph
from repro.graph.generators import golden_suite

pytestmark = pytest.mark.fast

GRAPHS = golden_suite()


def _edge_set(g):
    return {tuple(r) for r in np.asarray(g.edges).tolist()}


def _absent_pairs(g, rng, k):
    present = _edge_set(g)
    out = []
    for u in range(g.n):
        for v in range(u + 1, g.n):
            if (u, v) not in present:
                out.append((u, v))
    rng.shuffle(out)
    return out[:k]


def _assert_matches_fresh(dec, cfg, label):
    """The updated artifact vs a fresh decompose of its own graph."""
    fresh = decompose(dec.problem.g, cfg)
    np.testing.assert_array_equal(np.asarray(dec.core),
                                  np.asarray(fresh.core),
                                  err_msg=f"{label}: core")
    np.testing.assert_array_equal(np.asarray(dec.peel_value),
                                  np.asarray(fresh.peel_value),
                                  err_msg=f"{label}: peel_value")
    if cfg.hierarchy == "fused":
        np.testing.assert_array_equal(np.asarray(dec.uf_parent),
                                      np.asarray(fresh.uf_parent),
                                      err_msg=f"{label}: uf_parent")
        np.testing.assert_array_equal(np.asarray(dec.uf_L),
                                      np.asarray(fresh.uf_L),
                                      err_msg=f"{label}: uf_L")
        np.testing.assert_array_equal(np.asarray(dec.tree.parent),
                                      np.asarray(fresh.tree.parent),
                                      err_msg=f"{label}: tree parent")
        np.testing.assert_array_equal(np.asarray(dec.tree.level),
                                      np.asarray(fresh.tree.level),
                                      err_msg=f"{label}: tree level")
        kmax = int(np.asarray(fresh.core).max(initial=0))
        for c in {1, max(kmax, 1)}:
            np.testing.assert_array_equal(dec.cut(c), fresh.cut(c),
                                          err_msg=f"{label}: cut({c})")


# ---------------------------------------------------------------------------
# GraphDelta
# ---------------------------------------------------------------------------

def test_graphdelta_canonicalizes_and_orders_ops():
    d = GraphDelta(insert=np.array([[5, 2]]), delete=np.array([[1, 0]]))
    np.testing.assert_array_equal(d.insert, [[2, 5]])
    np.testing.assert_array_equal(d.delete, [[0, 1]])
    assert d.n_ops == 2
    # deletes drain before inserts: freed capacity first, strictness after
    assert [op for op, _, _ in d.ops()] == ["delete", "insert"]


def test_graphdelta_rejects_self_loops():
    with pytest.raises(ValueError, match="self-loop"):
        GraphDelta(insert=np.array([[3, 3]]))
    with pytest.raises(ValueError, match="self-loop"):
        GraphDelta(delete=np.array([[0, 0]]))


def test_update_rejects_drifted_view():
    g = GRAPHS["two_triangles"]()
    cfg = NucleusConfig(r=1, s=2, backend="dense", hierarchy="fused")
    dec = decompose(g, cfg)
    present = next(iter(_edge_set(g)))
    with pytest.raises(ValueError, match="insert of present edge"):
        dec.update(GraphDelta(insert=np.array([present])))
    absent = _absent_pairs(g, np.random.default_rng(0), 1)[0]
    with pytest.raises(ValueError, match="delete of absent edge"):
        dec.update(GraphDelta(delete=np.array([absent])))
    with pytest.raises(ValueError, match="out of range"):
        dec.update(GraphDelta(insert=np.array([[0, g.n]])))


# ---------------------------------------------------------------------------
# Parity vs fresh decompose()
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r,s", sorted(SUPPORTED_RS))
@pytest.mark.parametrize("gname", ["bowtie_plus", "er20"])
def test_update_parity_randomized(gname, r, s):
    """Randomized insert/delete sequence, parity checked after every
    delta — the artifact is maintained through the whole stream, not
    just one hop."""
    rng = np.random.default_rng(7)
    cfg = NucleusConfig(r=r, s=s, backend="dense", hierarchy="fused")
    g = GRAPHS[gname]()
    dec = decompose(g, cfg)
    for step in range(6):
        g = dec.problem.g
        present = sorted(_edge_set(g))
        absent = _absent_pairs(g, rng, 1)
        # keep the graph editable in both directions
        if absent and (rng.random() < 0.5 or len(present) <= 2):
            delta = GraphDelta(insert=np.array([absent[0]]))
        else:
            pair = present[rng.integers(len(present))]
            delta = GraphDelta(delete=np.array([pair]))
        dec = dec.update(delta)
        assert dec.rounds == -1 and dec.order_round is None
        _assert_matches_fresh(dec, cfg, f"{gname} r{r}s{s} step{step}")


def mixed_cases():
    """Each supported (r, s), plus (2, 3) with the forest patch's link
    fixpoint held to an 8-slot worklist (pending links refill it)."""
    for r, s in sorted(SUPPORTED_RS):
        yield pytest.param(r, s, None, id=f"{r}-{s}")
    yield pytest.param(2, 3, 8, id="2-3-w8")


@pytest.mark.parametrize("r,s,width", mixed_cases())
def test_update_batched_delta_mixed_ops(r, s, width, monkeypatch):
    if width is not None:
        monkeypatch.setattr(streaming, "_fixpoint_padded", partial(
            streaming._fixpoint_padded, link_width=width))
    rng = np.random.default_rng(3)
    cfg = NucleusConfig(r=r, s=s, backend="dense", hierarchy="fused")
    g = GRAPHS["fig1"]()
    dec = decompose(g, cfg)
    dels = sorted(_edge_set(g))[:2]
    ins = _absent_pairs(g, rng, 2)
    delta = GraphDelta(insert=np.array(ins), delete=np.array(dels))
    dec = dec.update(delta)
    assert dec.update_stats.ops == delta.n_ops == 4
    _assert_matches_fresh(dec, cfg, f"batched r{r}s{s}")


def test_update_without_hierarchy():
    cfg = NucleusConfig(r=2, s=3, backend="dense", hierarchy="none")
    g = GRAPHS["two_triangles"]()
    dec = decompose(g, cfg)
    pair = _absent_pairs(g, np.random.default_rng(1), 1)[0]
    dec = dec.update(GraphDelta(insert=np.array([pair])))
    assert dec.uf_parent is None
    _assert_matches_fresh(dec, cfg, "no-hierarchy")


def test_update_insert_delete_roundtrip_restores_core():
    cfg = NucleusConfig(r=1, s=2, backend="dense", hierarchy="fused")
    g = GRAPHS["er20"]()
    dec0 = decompose(g, cfg)
    pair = _absent_pairs(g, np.random.default_rng(2), 1)[0]
    dec1 = dec0.update(GraphDelta(insert=np.array([pair])))
    dec2 = dec1.update(GraphDelta(delete=np.array([pair])))
    np.testing.assert_array_equal(np.asarray(dec2.core),
                                  np.asarray(dec0.core))
    np.testing.assert_array_equal(np.asarray(dec2.uf_parent),
                                  np.asarray(dec0.uf_parent))
    np.testing.assert_array_equal(np.asarray(dec2.uf_L),
                                  np.asarray(dec0.uf_L))


def test_update_localizes_small_edits():
    """The telemetry contract behind the stream bench: an edit in a
    low-core region never floods across a higher-core bottleneck — the
    K8's vertices are not candidates when the pendant path changes."""
    cfg = NucleusConfig(r=1, s=2, backend="dense", hierarchy="none")
    k8 = [[i, j] for i in range(8) for j in range(i + 1, 8)]
    dec = decompose(make_graph(11, np.array(k8 + [[8, 9], [9, 10]])), cfg)
    dec = dec.update(GraphDelta(insert=np.array([[8, 10]])))
    stats = dec.update_stats
    assert stats.candidates <= 3, stats  # the path triangle, not the K8
    _assert_matches_fresh(dec, cfg, "pendant-insert")


# ---------------------------------------------------------------------------
# Error paths
# ---------------------------------------------------------------------------

def test_update_requires_exact_method():
    cfg = NucleusConfig(r=2, s=3, method="approx", delta=0.25,
                        backend="dense", hierarchy="none")
    dec = decompose(GRAPHS["two_triangles"](), cfg)
    with pytest.raises(ValueError, match="exact"):
        dec.update(GraphDelta(insert=np.array([[0, 5]])))


def test_update_requires_supported_rs():
    cfg = NucleusConfig(r=3, s=4, backend="dense", hierarchy="none")
    dec = decompose(GRAPHS["planted40"](), cfg)
    with pytest.raises(ValueError, match=r"\(r, s\)"):
        dec.update(GraphDelta(insert=np.array([[0, 1]])))


def test_update_requires_fused_or_no_hierarchy():
    cfg = NucleusConfig(r=2, s=3, backend="dense", hierarchy="replay")
    dec = decompose(GRAPHS["two_triangles"](), cfg)
    with pytest.raises(ValueError, match="fused"):
        dec.update(GraphDelta(insert=np.array([[0, 5]])))


def test_update_requires_attached_problem():
    from repro.core.api import Decomposition
    cfg = NucleusConfig(r=2, s=3, backend="dense", hierarchy="fused")
    dec = decompose(GRAPHS["two_triangles"](), cfg)
    reloaded = Decomposition.from_json(dec.to_json())
    with pytest.raises(ValueError, match="re-decompose"):
        reloaded.update(GraphDelta(insert=np.array([[0, 5]])))
