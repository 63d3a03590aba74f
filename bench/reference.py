"""The plain reference the benchmark judges ``correct`` by.

Straightforward numpy (and scipy's connected components) of the same
semantics as the program, importing nothing of it:

* ``incidence``: the (2, 3) incidence of a graph — r-cliques are its
  edges, lexsorted; s-cliques are its triangles; each triangle lists the
  ids of its three edges; ``deg0`` counts the triangles on each edge.
* ``peel``: the exact bucketed parallel peel.  Each round the level rises
  to the least live degree, every live r-clique whose degree is at most
  the level peels with that level as its core number, every s-clique with
  a peeled member dies, and each live member of a dying s-clique loses
  one degree.  The round count is the number of such rounds.
* ``partition``: the c-(r, s) nuclei: r-cliques with core >= c, joined
  when they share an s-clique, as connected components.
* ``nuclei``: each nucleus as its vertex set, its r-clique count and the
  number of graph edges inside the vertex set.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components


@dataclasses.dataclass
class Incidence:
    n: int
    edges: np.ndarray      # (n_r, 2) int64, lexsorted, lo < hi
    triangles: np.ndarray  # (n_s, 3) int64 vertex triples a < b < c, lexsorted
    tri_edges: np.ndarray  # (n_s, 3) int64 edge ids of (a,b), (a,c), (b,c)
    deg0: np.ndarray       # (n_r,) int64 triangles on each edge

    @property
    def n_r(self) -> int:
        return int(self.edges.shape[0])

    @property
    def n_s(self) -> int:
        return int(self.triangles.shape[0])


def _edge_ids(edges: np.ndarray, n: int, lo: np.ndarray,
              hi: np.ndarray) -> np.ndarray:
    """Row index of each (lo, hi) in the lexsorted ``edges``; -1 if absent."""
    keys = edges[:, 0] * n + edges[:, 1]
    q = lo * n + hi
    pos = np.searchsorted(keys, q)
    pos = np.minimum(pos, max(keys.shape[0] - 1, 0))
    hit = keys.shape[0] > 0
    return np.where(hit & (keys[pos] == q), pos, -1) if hit \
        else np.full(q.shape, -1, np.int64)


def incidence(n: int, edges: np.ndarray) -> Incidence:
    """Triangles of the graph with canonical ``edges``, by the degree
    orientation: each triangle is found once, from its lowest-ranked
    vertex, as a pair of that vertex's out-neighbours joined by an edge."""
    edges = np.asarray(edges, np.int64)
    n_r = edges.shape[0]
    deg = np.bincount(edges.reshape(-1), minlength=n)
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
    u, v = edges[:, 0], edges[:, 1]
    fwd = rank[u] < rank[v]
    src = np.where(fwd, u, v)
    dst = np.where(fwd, v, u)
    order = np.lexsort((rank[dst], src))
    src, dst = src[order], dst[order]
    outdeg = np.bincount(src, minlength=n)
    start = np.concatenate([[0], np.cumsum(outdeg)])
    # every ordered pair (x, y) of out-neighbours of w with rank x < rank y:
    # out-lists are sorted by rank, so y runs over the list after x
    per_edge = start[src + 1] - (np.arange(src.shape[0]) + 1)
    total = int(per_edge.sum())
    first = np.repeat(np.arange(src.shape[0]), per_edge)
    offs = np.arange(total) - np.repeat(np.cumsum(per_edge) - per_edge,
                                        per_edge)
    second = first + 1 + offs
    w, x, y = src[first], dst[first], dst[second]
    xy_id = _edge_ids(edges, n, np.minimum(x, y), np.maximum(x, y))
    keep = xy_id >= 0
    tri = np.sort(np.stack([w[keep], x[keep], y[keep]], axis=1), axis=1)
    tri = tri[np.lexsort((tri[:, 2], tri[:, 1], tri[:, 0]))]
    a, b, c = tri[:, 0], tri[:, 1], tri[:, 2]
    tri_edges = np.stack([_edge_ids(edges, n, a, b), _edge_ids(edges, n, a, c),
                          _edge_ids(edges, n, b, c)], axis=1)
    deg0 = np.bincount(tri_edges.reshape(-1), minlength=n_r)
    return Incidence(n=n, edges=edges, triangles=tri, tri_edges=tri_edges,
                     deg0=deg0)


def peel(inc: Incidence) -> Tuple[np.ndarray, int]:
    """(core numbers, rounds) of the exact bucketed parallel peel."""
    n_r = inc.n_r
    te = inc.tri_edges
    deg = inc.deg0.copy()
    core = np.full(n_r, -1, np.int64)
    peeled = np.zeros(n_r, bool)
    alive = np.ones(te.shape[0], bool)
    # membership CSR: r-clique -> the s-cliques holding it
    sids = np.argsort(te.reshape(-1), kind="stable") // 3
    offsets = np.concatenate([[0], np.cumsum(inc.deg0)])
    level = 0
    rounds = 0
    while not peeled.all():
        level = max(level, int(deg[~peeled].min()))
        now = np.flatnonzero(~peeled & (deg <= level))
        core[now] = level
        peeled[now] = True
        touched = np.concatenate(
            [sids[offsets[i]:offsets[i + 1]] for i in now]) if now.size \
            else np.zeros(0, np.int64)
        dying = np.unique(touched)
        dying = dying[alive[dying]]
        alive[dying] = False
        delta = np.bincount(te[dying].reshape(-1), minlength=n_r)
        deg = np.where(peeled, deg, deg - delta)
        rounds += 1
    return core, rounds


def partition(inc: Incidence, core: np.ndarray, c: int) -> np.ndarray:
    """Canonical labels of the c-nuclei: -1 where core < c, else the
    component's index in order of its first r-clique."""
    te = inc.tri_edges
    pairs = np.concatenate([te[:, [0, 1]], te[:, [0, 2]], te[:, [1, 2]]])
    ok = (core[pairs[:, 0]] >= c) & (core[pairs[:, 1]] >= c)
    pairs = pairs[ok]
    n_r = inc.n_r
    adj = coo_matrix((np.ones(pairs.shape[0], np.int8),
                      (pairs[:, 0], pairs[:, 1])), shape=(n_r, n_r))
    _, comp = connected_components(adj, directed=False)
    return canonical_labels(np.where(core >= c, comp, -1))


def canonical_labels(labels: np.ndarray) -> np.ndarray:
    """Relabel by order of first appearance; -1 stays -1."""
    labels = np.asarray(labels, np.int64)
    out = np.full(labels.shape, -1, np.int64)
    live = labels >= 0
    if live.any():
        uniq, first, inv = np.unique(labels[live], return_index=True,
                                     return_inverse=True)
        rank = np.empty(uniq.shape[0], np.int64)
        rank[np.argsort(first, kind="stable")] = np.arange(uniq.shape[0])
        out[live] = rank[inv.reshape(-1)]
    return out


def nuclei(inc: Incidence, labels: np.ndarray) -> List[Tuple]:
    """Each nucleus of a canonical ``labels`` as (vertex tuple, r-clique
    count, edges inside the vertex set), sorted."""
    out = []
    edges = inc.edges
    for lab in np.unique(labels[labels >= 0]):
        members = labels == lab
        verts = np.unique(edges[members].reshape(-1))
        inside = np.isin(edges[:, 0], verts) & np.isin(edges[:, 1], verts)
        out.append((tuple(int(x) for x in verts), int(members.sum()),
                    int(inside.sum())))
    return sorted(out)


def levels(core: np.ndarray) -> List[int]:
    """The distinct positive core numbers: every level with a nucleus."""
    return [int(c) for c in np.unique(core) if c > 0]


def query_levels(core: np.ndarray, spec: List) -> List[int]:
    """The levels a job queries: "lowest" is the least positive core
    number, a fraction f is floor(f * kmax); level 0 is dropped."""
    cs = levels(core)
    if not cs:
        return []
    kmax = cs[-1]
    want = {cs[0] if q == "lowest" else int(float(q) * kmax) for q in spec}
    return sorted(want - {0})

