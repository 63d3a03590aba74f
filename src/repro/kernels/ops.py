"""Jitted public wrappers around the Pallas kernels (padding + dispatch).

Callers use these; they handle shape padding to kernel tile multiples and
fall back to the jnp reference implementation for shapes where a kernel
launch cannot win (tiny inputs).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention
from .segment_sum import segment_sum_sorted
from .tricount import (tricount_per_edge, tricount_oriented as
                       _tricount_oriented, triangle_count)


def _pad_to(x: jnp.ndarray, axis: int, multiple: int, value=0):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), pad


@partial(jax.jit, static_argnames=("tile", "interpret"))
def tricount(adj: jnp.ndarray, tile: int = 128,
             interpret: bool | None = None) -> jnp.ndarray:
    """Per-edge triangle counts (the kernel pads to the tile size itself)."""
    return tricount_per_edge(adj.astype(jnp.float32), tile=tile,
                             interpret=interpret)


@partial(jax.jit, static_argnames=("tile", "interpret"))
def tricount_oriented(adj: jnp.ndarray, tile: int = 128,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Per-DAG-edge 3-clique extension counts (D @ Dᵀ) ⊙ D, any n."""
    return _tricount_oriented(adj.astype(jnp.float32), tile=tile,
                              interpret=interpret)


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                   "interpret"))
def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = True, block_q: int = 128, block_k: int = 128,
              interpret: bool | None = None) -> jnp.ndarray:
    """Flash attention with seq padding (pad keys get -inf via causal/len)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    qp, pq = _pad_to(q, 2, block_q)
    kp, pk = _pad_to(k, 2, block_k)
    vp, _ = _pad_to(v, 2, block_k)
    # pk is the static pad amount (shape arithmetic), not a tracer — the
    # taint analysis can't see through _pad_to's return value
    if pk:  # nucleuslint: disable=NL102
        # disable padded keys by pushing them outside the causal horizon; for
        # non-causal, mask via a huge negative on k rows is handled by zero
        # value rows + renormalization being exact only when causal. Callers
        # with non-causal ragged keys should pre-mask.
        assert causal, "non-causal padded attention: pre-pad keys yourself"
    out = flash_attention(qp, kp, vp, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=interpret)
    return out[:, :, :Sq]


def segment_sum(data: jnp.ndarray, ids: jnp.ndarray, n_segments: int,
                block_n: int = 128, chunk_e: int = 512,
                max_chunks: int | None = None,
                interpret: bool | None = None) -> jnp.ndarray:
    """Sorted-segment sum of (E, d) rows -> (n_segments, d); pads rows
    (id = n_segments) and segments, and feeds the kernel lane-major."""
    dp, _ = _pad_to(data.T, 1, chunk_e)
    idp, _ = _pad_to(ids, 0, chunk_e, value=n_segments)
    n_pad = -(-n_segments // block_n) * block_n + block_n  # room for pad ids
    out = segment_sum_sorted(dp, idp.astype(jnp.int32), n_pad,
                             block_n=block_n, chunk_e=chunk_e,
                             max_chunks=max_chunks, interpret=interpret)
    return out[:, :n_segments].T
