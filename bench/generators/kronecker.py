"""Graph500 Kronecker graphs, the benchmark's own copy of the generator.

Kept here so that a change to the program's generator cannot move the
yardstick.  Graph500 specification, section "Kronecker generator":
2**scale vertices, edge_factor * 2**scale sampled edges, and per bit level
one quadrant of the adjacency matrix drawn with the initiator
probabilities A, B, C, D (0.57, 0.19, 0.19, 0.05); vertex ids are then
permuted at random.  Self-loops are dropped; duplicate edges collapse when
the edge list is canonicalized.

A configuration names this generator with ``"generator": "kronecker"``
in its ``graph`` group, beside ``scale``, ``edge_factor`` and
``initiator``.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from graphs import canonical_edges


def kronecker_edges(scale: int, edge_factor: int, initiator,
                    rng: np.random.Generator) -> np.ndarray:
    """(k, 2) int64 sampled edges of one Kronecker graph, self-loops
    dropped, duplicates kept."""
    a, b, c, _d = (float(p) for p in initiator)
    n = 1 << scale
    m = edge_factor * n
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
    return np.stack([u[keep], v[keep]], axis=1)


def graph(params: Dict[str, Any], rng: np.random.Generator
          ) -> Tuple[int, np.ndarray]:
    """(vertex count, canonical edges) of one graph of the configuration's
    ``graph`` group."""
    scale = int(params["scale"])
    return 1 << scale, canonical_edges(kronecker_edges(
        scale, int(params["edge_factor"]), params["initiator"], rng))
