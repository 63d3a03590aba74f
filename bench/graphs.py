"""Seeds and edge lists shared by every graph generator and loop."""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator keyed by ``seed`` and a stream index path.  Any
    Python int works as ``seed`` (negative ones are read modulo 2**64)."""
    mask = (1 << 64) - 1
    return np.random.default_rng([int(seed) & mask]
                                 + [int(s) & mask for s in stream])


def canonical_edges(edges: np.ndarray) -> np.ndarray:
    """Undirected edge list as unique (lo, hi) rows, lo < hi, lexsorted."""
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    lo = np.minimum(e[:, 0], e[:, 1])
    hi = np.maximum(e[:, 0], e[:, 1])
    keep = lo != hi
    pairs = np.stack([lo[keep], hi[keep]], axis=1)
    return np.unique(pairs, axis=0)


def relabel(edges: np.ndarray, perm: np.ndarray) -> np.ndarray:
    """The canonical edges of the graph with vertex ``v`` renamed
    ``perm[v]``: an isomorphic graph, whose edge list differs."""
    return canonical_edges(perm[np.asarray(edges, np.int64)])
