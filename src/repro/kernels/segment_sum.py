"""Pallas TPU kernel: sorted-segment sum (the GNN / EmbeddingBag hot loop).

`jax.ops.segment_sum` lowers to scatter-add, which serializes on TPU.  For
SORTED segment ids (what a CSR edge array gives for free) the reduction is a
band-structured one-hot matmul:

    out[:, n0:n1] = Σ_chunks data_chunk @ onehot(ids_chunk, [n0, n1))ᵀ

Grid = (out_blocks, chunks_per_block).  A scalar-prefetch array `chunk0[i]`
(first input chunk touching output block i, via searchsorted on the host/XLA
side) makes the input BlockSpec index_map *data-dependent*: each output block
only visits chunks that can intersect it — O(E/C + N/B) grid steps total
instead of O(E/C * N/B).  The one-hot contraction runs on the MXU.

max_chunks bounds the chunks any single output block can span; chunks beyond
a block's live range are skipped with @pl.when (no memory traffic: the
index_map clamps to the last live chunk).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 128
DEFAULT_CHUNK_E = 512


def sorted_ids_plan(ids: np.ndarray, n_segments: int,
                    block_n: int = DEFAULT_BLOCK_N,
                    chunk_e: int = DEFAULT_CHUNK_E,
                    e_pad: int | None = None,
                    n_seg_pad: int | None = None):
    """Pad a concrete sorted id array so `segment_sum_sorted` jits.

    Returns ``(ids_padded, n_seg_pad, max_chunks)``: ids padded to a chunk_e
    multiple (pad id = n_seg_pad, outside every output block), the segment
    count padded to a block_n multiple, and the static per-block chunk-span
    bound the kernel needs under jit.  ``e_pad``/``n_seg_pad`` override the
    minimal pads (the Session passes its pow2 bucket shapes so same-bucket
    problems share one executable).  Everything here is eager numpy — call
    it once at plan-build time, then feed the jitted hot loop.
    """
    ids = np.asarray(ids, np.int32)
    if n_seg_pad is None:
        n_seg_pad = -(-max(n_segments, 1) // block_n) * block_n
    assert n_seg_pad % block_n == 0 and n_seg_pad >= n_segments
    E = ids.shape[0]
    E_pad = e_pad if e_pad is not None else -(-max(E, 1) // chunk_e) * chunk_e
    assert E_pad % chunk_e == 0 and E_pad >= E
    ids_padded = np.full(E_pad, n_seg_pad, np.int32)
    ids_padded[:E] = ids
    # same intersection logic as segment_sum_sorted, concretely
    bounds_lo = np.arange(n_seg_pad // block_n, dtype=np.int64) * block_n
    chunk_first = ids_padded[::chunk_e]
    chunk_last = ids_padded[chunk_e - 1::chunk_e]
    c0 = np.searchsorted(chunk_last, bounds_lo, side="left")
    c1 = np.searchsorted(chunk_first, bounds_lo + block_n, side="left")
    max_chunks = max(int(np.max(np.maximum(c1 - c0, 0), initial=0)), 1)
    return ids_padded, n_seg_pad, max_chunks


def _segsum_kernel(chunk0_ref, nchunks_ref, ids_ref, data_ref, out_ref,
                   acc_ref, *, block_n: int, chunk_e: int, max_chunks: int):
    i = pl.program_id(0)   # output block
    j = pl.program_id(1)   # chunk-within-block

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < nchunks_ref[i])
    def _body():
        ids = ids_ref[...]                       # (1, chunk_e) int32
        data = data_ref[...]                     # (d, chunk_e)
        n0 = i * block_n
        rows = n0 + jax.lax.broadcasted_iota(jnp.int32,
                                             (block_n, chunk_e), 0)
        onehot = (ids == rows).astype(jnp.float32)   # (block_n, chunk_e)
        # (d, chunk_e) x (block_n, chunk_e)^T -> (d, block_n)
        acc_ref[...] += jax.lax.dot_general(
            data.astype(jnp.float32), onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == max_chunks - 1)
    def _finish():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def segment_sum_sorted(data: jnp.ndarray, ids: jnp.ndarray, n_segments: int,
                       block_n: int = DEFAULT_BLOCK_N,
                       chunk_e: int = DEFAULT_CHUNK_E,
                       max_chunks: int | None = None,
                       interpret: bool | None = None) -> jnp.ndarray:
    """data: (d, E), ids: (E,) int32 SORTED ascending -> (d, n_segments).

    Both the per-edge operand and the result are lane-major (the edge and
    segment axes are minor): the TPU tiles the two minor dims of an HBM
    array by (8, 128), so an (E, 1) operand would be padded 128-fold.
    E % chunk_e == 0 and n_segments % block_n == 0 (pad at the wrapper; use
    id = n_segments for padding rows — they fall outside every block).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    d, E = data.shape
    assert E % chunk_e == 0 and n_segments % block_n == 0
    n_blocks = n_segments // block_n
    n_chunks_total = E // chunk_e
    # first/last chunk intersecting each output block (host-side searchsorted
    # on chunk boundary ids — XLA ops, cheap, jit-compatible)
    bounds_lo = jnp.arange(n_blocks, dtype=jnp.int32) * block_n
    bounds_hi = bounds_lo + block_n
    chunk_first_id = ids[::chunk_e]                     # (n_chunks,)
    chunk_last_id = ids[chunk_e - 1::chunk_e]
    # chunk k intersects block i iff first_id < hi and last_id >= lo
    c0 = jnp.searchsorted(chunk_last_id, bounds_lo, side="left")
    c1 = jnp.searchsorted(chunk_first_id, bounds_hi, side="left")
    nchunks = jnp.maximum(c1 - c0, 0).astype(jnp.int32)
    if max_chunks is None:
        # exact bound requires concrete ids (eager call); under jit pass an
        # explicit static bound (e.g. from the data pipeline's degree cap)
        if isinstance(nchunks, jax.core.Tracer):
            raise ValueError("segment_sum_sorted under jit needs max_chunks")
        max_chunks = max(int(jnp.max(nchunks)), 1)
    c0 = jnp.minimum(c0, n_chunks_total - 1).astype(jnp.int32)
    nchunks = jnp.minimum(nchunks, max_chunks)

    grid = (n_blocks, max_chunks)
    ids2d = ids.reshape(1, E)

    def edge_map(i, j, chunk0_ref, nchunks_ref):
        k = chunk0_ref[i] + jnp.minimum(j, nchunks_ref[i] - 1)
        k = jnp.clip(k, 0, n_chunks_total - 1)
        return (0, k)

    def out_map(i, j, chunk0_ref, nchunks_ref):
        return (0, i)

    return pl.pallas_call(
        partial(_segsum_kernel, block_n=block_n, chunk_e=chunk_e,
                max_chunks=max_chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, chunk_e), edge_map),
                pl.BlockSpec((d, chunk_e), edge_map),
            ],
            out_specs=pl.BlockSpec((d, block_n), out_map),
            scratch_shapes=[pltpu.VMEM((d, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((d, n_segments), data.dtype),
        interpret=interpret,
        name="segment_sum_sorted",
    )(c0, nchunks, ids2d, data)
