"""Batched union-find on top of min-hooking connectivity.

The root of every set is the minimum member id — deterministic, so parallel
runs and the sequential oracle agree on representatives.

The functional API (``uf_create`` / ``uf_find_all`` / ``uf_union_edges``) is
pure and jit-compatible: state is a plain (n,) parent array, every op returns
a new array, and the underlying loops are ``lax.while_loop``s — this is the
form the fused hierarchy engine threads through its peel carry
(DESIGN.md §5).  ``BatchedUnionFind`` wraps it for eager host callers.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .connectivity import connected_components, pointer_jump
from .container import INT


def uf_create(n: int) -> jnp.ndarray:
    """Fresh parent array: every element its own root."""
    return jnp.arange(n, dtype=INT)


def uf_find_all(parent: jnp.ndarray) -> jnp.ndarray:
    """Fully resolved parent array (parent[parent] == parent)."""
    return pointer_jump(parent)


def uf_union_edges(parent: jnp.ndarray, u: jnp.ndarray,
                   v: jnp.ndarray) -> jnp.ndarray:
    """Unite endpoints of all edges at once; returns resolved parents.

    Self-edges are no-ops, so fixed-shape callers mask dead slots to (0, 0).
    """
    with jax.named_scope("repro.uf_union"):
        return connected_components(int(parent.shape[0]), u, v, init=parent)


@dataclasses.dataclass
class BatchedUnionFind:
    parent: jnp.ndarray  # (n,) int32, parent[i] <= i invariant after resolve

    @classmethod
    def create(cls, n: int) -> "BatchedUnionFind":
        return cls(parent=uf_create(n))

    def find_all(self) -> jnp.ndarray:
        self.parent = uf_find_all(self.parent)
        return self.parent

    def union_edges(self, u: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
        """Unite endpoints of all edges at once; returns resolved labels."""
        self.parent = uf_union_edges(self.parent, u, v)
        return self.parent
