"""Graph generators for tests and benchmarks (SNAP graphs are offline)."""
from __future__ import annotations

import numpy as np

from .container import Graph, make_graph


def erdos_renyi(n: int, p: float, seed: int = 0) -> Graph:
    rng = np.random.default_rng(seed)
    iu = np.triu_indices(n, k=1)
    mask = rng.random(iu[0].shape[0]) < p
    edges = np.stack([iu[0][mask], iu[1][mask]], axis=1)
    return make_graph(n, edges)


def erdos_renyi_sparse(n: int, m_target: int, seed: int = 0) -> Graph:
    """O(m) sampling for large sparse graphs."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n, size=2 * m_target)
    v = rng.integers(0, n, size=2 * m_target)
    keep = u != v
    return make_graph(n, np.stack([u[keep], v[keep]], axis=1)[:m_target])


def barabasi_albert(n: int, k: int, seed: int = 0) -> Graph:
    """Preferential attachment: power-law degrees (high clique counts)."""
    rng = np.random.default_rng(seed)
    targets = list(range(k))
    repeated: list[int] = list(range(k))
    edges = []
    for v in range(k, n):
        chosen = rng.choice(repeated, size=min(k, len(repeated)), replace=False)
        for t in set(int(c) for c in chosen):
            edges.append((v, t))
            repeated.append(t)
            repeated.append(v)
    return make_graph(n, np.asarray(edges, dtype=np.int64))


def kronecker(scale: int, edge_factor: int = 16, seed: int = 0) -> Graph:
    """Graph500 Kronecker graph: 2**scale vertices, edge_factor * 2**scale
    sampled edges before self-loops and duplicates are dropped.

    Each edge picks one quadrant of the adjacency matrix per bit level
    with the Graph500 initiator probabilities A, B, C, D = 0.57, 0.19,
    0.19, 0.05; vertex ids are then permuted at random, so ids carry no
    degree information.  Vectorized over edges (O(m * scale) numpy work).
    """
    a, b, c = 0.57, 0.19, 0.19
    n = 1 << scale
    m = edge_factor * n
    rng = np.random.default_rng(seed)
    c_norm = c / (1.0 - (a + b))
    a_norm = a / (a + b)
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for bit in range(scale):
        u_bit = rng.random(m) > (a + b)
        v_bit = rng.random(m) > np.where(u_bit, c_norm, a_norm)
        u |= u_bit.astype(np.int64) << bit
        v |= v_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    u, v = perm[u], perm[v]
    keep = u != v
    return make_graph(n, np.stack([u[keep], v[keep]], axis=1))


def planted_cliques(n: int, clique_sizes, p_background: float = 0.01,
                    seed: int = 0) -> Graph:
    """Background ER graph + planted cliques => a known nested-density structure."""
    rng = np.random.default_rng(seed)
    g = erdos_renyi(n, p_background, seed=seed)
    edges = [np.asarray(g.edges)]
    start = 0
    for size in clique_sizes:
        members = np.arange(start, min(start + size, n))
        iu = np.triu_indices(len(members), k=1)
        edges.append(np.stack([members[iu[0]], members[iu[1]]], axis=1))
        start += max(1, size // 2)  # overlap consecutive cliques
    return make_graph(n, np.concatenate(edges, axis=0))


def paper_figure1_like() -> Graph:
    """A small graph with the nested (1,3)-nucleus structure of paper Fig. 1.

    Vertices 0-3: a K5-ish dense core (core 4 region needs every vertex in >=4
    triangles); 4-6: triangle-rich ring attached to the core; 7: bridge vertex
    in 2 triangles; 8: vertex in exactly 1 triangle.
    """
    edges = [
        # dense core: K5 on 0..4
        (0, 1), (0, 2), (0, 3), (0, 4), (1, 2), (1, 3), (1, 4), (2, 3), (2, 4), (3, 4),
        # middle shell: triangles sharing edges with the core boundary
        (3, 5), (4, 5), (5, 6), (3, 6), (5, 7), (6, 7),
        # outer: one triangle
        (7, 8), (6, 8),
    ]
    return make_graph(9, np.asarray(edges, dtype=np.int64))


def golden_suite():
    """The golden-fixture graph suite: name -> zero-arg Graph factory.

    The SINGLE definition shared by tools/regen_golden.py (writes
    tests/golden/*.json) and tests/test_golden.py (re-derives and checks) —
    a seed or parameter drifting between writer and checker would otherwise
    surface as a misleading backend-mismatch failure.
    """
    return {
        "triangle": lambda: tiny_named("triangle"),
        "k4": lambda: tiny_named("k4"),
        "path4": lambda: tiny_named("path4"),
        "two_triangles": lambda: tiny_named("two_triangles"),
        "bowtie_plus": lambda: tiny_named("bowtie_plus"),
        "fig1": paper_figure1_like,
        # seeded generators: deterministic, big enough for multi-level trees
        "er20": lambda: erdos_renyi(20, 0.35, seed=1),
        "planted40": lambda: planted_cliques(40, [8, 6, 5], 0.05, seed=3),
    }


GOLDEN_RS = [(1, 2), (2, 3), (3, 4)]


def tiny_named(name: str) -> Graph:
    if name == "triangle":
        return make_graph(3, [(0, 1), (1, 2), (0, 2)])
    if name == "k4":
        return make_graph(4, [(0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3)])
    if name == "path4":
        return make_graph(4, [(0, 1), (1, 2), (2, 3)])
    if name == "two_triangles":
        # two triangles sharing one vertex
        return make_graph(5, [(0, 1), (1, 2), (0, 2), (2, 3), (3, 4), (2, 4)])
    if name == "bowtie_plus":
        # two K4s joined by an edge
        e = [(0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3),
             (4, 5), (4, 6), (4, 7), (5, 6), (5, 7), (6, 7), (3, 4)]
        return make_graph(8, e)
    raise ValueError(name)
