"""The (r, s) incidence structure: the paper's multi-level hash tables, dense.

Materialized ONCE per problem (the same O(m * alpha^{s-2}) space the paper's
L_i tables occupy), it drives every later stage with gathers/segment-sums:

  r_cliques   (n_r, r)  lexicographically sorted unique rows; id = row index
  inc_rid     (n_s, C)  the C = C(s, r) member r-clique ids of each s-clique
  mem CSR               r-clique id -> incident s-clique ids
  deg0        (n_r,)    initial s-clique-degree of each r-clique

Three builders produce bit-identical output (DESIGN.md §7, §13):

  * ``build="eager"``   — one level-synchronous expansion over all source
    vertices at once, one concatenated sort-join.  Fastest when the
    intermediate candidate arrays fit comfortably in memory.
  * ``build="chunked"`` — the memory-bounded pipeline: the level-1 frontier
    is split into source-vertex chunks (sized from ``memory_budget_bytes``),
    each chunk runs the same fixed-shape expansion independently (the DAG
    orientation makes chunks duplicate-free), and the final arrays are
    assembled with a two-pass count-then-fill build instead of one giant
    concatenate.  On the (2,3) hot path the count pass routes through the
    Pallas ``tricount_oriented`` boolean-tile kernel (jnp oracle fallback),
    so allocation sizes come off the MXU without materializing a candidate
    array.
  * ``build="sharded"`` — the distributed build (``repro.distbuild``,
    DESIGN.md §13): budget-sized chunks are assigned to shards by a work
    planner, each shard expands its own contiguous seed range, and the
    incidence arrays are assembled slab-by-slab with a two-pass
    count-then-fill exchange — no global concatenate, no single-host
    ``csr_from_pairs``.

Peak intermediate memory is tracked by both builders (``build_stats`` on the
returned problem) so the ``build`` benchmark lane can report the headroom.
"""
from __future__ import annotations

import dataclasses
from math import comb
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..graph import (Graph, INT, csr_from_pairs, iter_clique_chunks,
                     sort_join, subset_columns)
from ..graph.cliques import expand_levels, lexsort_rows, sort_join_np
from ..graph.orientation import degree_rank, approx_degeneracy_rank
from ..graph.container import Digraph, orient
from .trace import span

BUILDS = ("eager", "chunked", "sharded")
# default memory budget for build="chunked" when the caller names neither a
# budget nor a chunk size: enough for the dense (2,3) fast path at n ~ 4.5k
DEFAULT_BUILD_BUDGET = 256 << 20

# candidate orientations tried by pick_rank, in tie-break priority order
ORIENTATIONS = (("degree", degree_rank),
                ("approx_degeneracy", approx_degeneracy_rank))


@dataclasses.dataclass
class NucleusProblem:
    g: Graph
    r: int
    s: int
    r_cliques: jnp.ndarray      # (n_r, r) int32, lexsorted rows
    inc_rid: jnp.ndarray        # (n_s, C) int32
    mem_offsets: jnp.ndarray    # (n_r + 1,) int32
    mem_sids: jnp.ndarray       # (n_s * C,) int32
    deg0: jnp.ndarray           # (n_r,) int32
    # which orientation produced the DAG the cliques were listed from —
    # reproducibility metadata ("degree" | "approx_degeneracy" | "caller");
    # eager and chunked builders must agree (tests assert it)
    orientation: str = "degree"
    # builder telemetry: {"build", "chunk_size", "n_chunks",
    #  "peak_intermediate_bytes", "memory_budget_bytes", "fastpath"};
    # NOT part of the byte-identity contract
    build_stats: Optional[Dict[str, Any]] = None

    @property
    def n_r(self) -> int:
        return int(self.r_cliques.shape[0])

    @property
    def n_s(self) -> int:
        return int(self.inc_rid.shape[0])

    @property
    def n_sub(self) -> int:
        return comb(self.s, self.r)


def pick_rank(g: Graph) -> Tuple[Digraph, str]:
    """Pick the orientation with the smaller max out-degree (cheap to try
    both).  Returns (digraph, orientation_name); ties go to the first
    candidate in ORIENTATIONS order, so the winner is deterministic and can
    be recorded on the problem."""
    oriented = [(orient(g, fn(g)), name) for name, fn in ORIENTATIONS]
    return min(oriented, key=lambda t: t[0].dmax)


def _resolve_digraph(g: Graph,
                     rank: Optional[jnp.ndarray]) -> Tuple[Digraph, str]:
    if rank is None:
        return pick_rank(g)
    return orient(g, rank), "caller"


def build_problem(g: Graph, r: int, s: int,
                  rank: Optional[jnp.ndarray] = None, *,
                  build: str = "eager",
                  memory_budget_bytes: Optional[int] = None,
                  chunk_size: Optional[int] = None,
                  fastpath: Optional[bool] = None,
                  shards: Optional[int] = None) -> NucleusProblem:
    """Build the (r, s) incidence structure.

    build="eager" is the one-burst builder; build="chunked" bounds peak
    intermediate memory by ``memory_budget_bytes`` (or an explicit
    ``chunk_size`` in source vertices); build="sharded" distributes the
    chunks over ``shards`` workers (default: ``jax.device_count()``) and
    assembles per-shard slabs directly (``repro.distbuild``).  All three
    produce bit-identical arrays.  ``fastpath`` forces the dense Pallas
    (2,3) count pass on/off (None = auto: on when (r, s) == (2, 3) and
    the dense blocks fit the budget; chunked builder only).
    """
    assert 1 <= r < s, (r, s)
    if build not in BUILDS:
        raise ValueError(f"build={build!r}; expected one of {BUILDS}")
    if shards is not None and build != "sharded":
        raise ValueError(
            f"shards={shards} is the sharded builder's worker count; set "
            f"build='sharded' or drop it (got build={build!r})")
    if build == "sharded" and fastpath:
        raise ValueError(
            "fastpath=True is the chunked builder's dense (2,3) count "
            "pass; it does not apply to build='sharded'")
    with span("build", n=g.n, m=int(g.edges.shape[0]), r=r, s=s,
              build=build):
        if build == "sharded":
            from ..distbuild import build_problem_sharded
            return build_problem_sharded(
                g, r, s, rank, n_shards=shards,
                memory_budget_bytes=memory_budget_bytes,
                chunk_size=chunk_size)
        with span("build.orient"):
            dg, orientation = _resolve_digraph(g, rank)
        if build == "eager":
            return _build_eager(g, r, s, dg, orientation)
        return _build_chunked(g, r, s, dg, orientation,
                              memory_budget_bytes=memory_budget_bytes,
                              chunk_size=chunk_size, fastpath=fastpath)


# ---------------------------------------------------------------------------
# Eager builder (the original one-burst pipeline)
# ---------------------------------------------------------------------------

def _build_eager(g: Graph, r: int, s: int, dg: Digraph,
                 orientation: str) -> NucleusProblem:
    levels, expand_peak = expand_levels(dg, jnp.arange(g.n, dtype=INT), [r, s])
    r_rows = levels[r]
    s_rows = levels[s]
    # r-clique table: rows are already unique; sort lexicographically for ids.
    order = lexsort_rows(r_rows) if r_rows.shape[0] else jnp.arange(0, dtype=INT)
    r_table = r_rows[order]
    n_r = int(r_table.shape[0])
    n_s = int(s_rows.shape[0])
    C = comb(s, r)
    join_bytes = 0
    if n_s:
        subs = [s_rows[:, list(cols)] for cols in subset_columns(s, r)]
        queries = jnp.concatenate(subs, axis=0)  # (C * n_s, r), grouped by combo
        join_bytes = 3 * int(queries.nbytes)  # queries + comb + sort perm
        ids = sort_join(r_table, queries)
        inc_rid = jnp.stack(jnp.split(ids, C), axis=1).astype(INT)  # (n_s, C)
    else:
        inc_rid = jnp.zeros((0, C), INT)
    flat_rid = inc_rid.reshape(-1)
    flat_sid = jnp.repeat(jnp.arange(n_s, dtype=INT), C, total_repeat_length=n_s * C)
    mem_offsets, mem_sids = csr_from_pairs(flat_rid, flat_sid, n_r)
    deg0 = jnp.zeros((n_r,), INT)
    if n_s:
        deg0 = deg0.at[flat_rid].add(1)
    stats = {"build": "eager", "chunk_size": g.n, "n_chunks": 1,
             "peak_intermediate_bytes": max(int(expand_peak), join_bytes),
             "memory_budget_bytes": None, "fastpath": False}
    return NucleusProblem(g=g, r=r, s=s, r_cliques=r_table, inc_rid=inc_rid,
                          mem_offsets=mem_offsets, mem_sids=mem_sids,
                          deg0=deg0, orientation=orientation,
                          build_stats=stats)


# ---------------------------------------------------------------------------
# Chunked builder (memory-bounded, two-pass count-then-fill)
# ---------------------------------------------------------------------------

def _derive_chunk_size(dg: Digraph, s: int, budget: int) -> int:
    """memory budget (bytes) -> source vertices per chunk (DESIGN.md §7).

    The deepest expansion level holds ~outdeg_avg * dmax^(s-2) partial rows
    per seed vertex; each row carries its (t,) vertex tuple plus a
    (dmax,)-wide candidate set at ~28 B per candidate element — the same
    constant the expansion's memory meter charges (int32 rows/gathers plus
    the int64 flat/query copies of the batched binary search).  The
    estimate is clamped to [1, n]: chunk_size=1 is the floor — one seed's
    expansion can exceed a pathological budget, which the builder reports
    in build_stats rather than failing.
    """
    dmax = max(dg.dmax, 1)
    n = max(dg.n, 1)
    outdeg = np.asarray(dg.outdeg)
    avg_out = max(float(outdeg.mean()), 1.0) if outdeg.size else 1.0
    rows_per_seed = avg_out * float(dmax) ** max(s - 2, 0)
    bytes_per_seed = 28.0 * (s + dmax) * rows_per_seed
    return int(np.clip(budget / max(bytes_per_seed, 1.0), 1, n))


def _fill_parts(parts: List[np.ndarray], width: int) -> np.ndarray:
    """Count-then-fill assembly: allocate the exact total once and copy each
    chunk in, releasing it — peak = total + one chunk, vs 2x total for a
    concatenate."""
    total = sum(int(p.shape[0]) for p in parts)
    out = np.empty((total, width), np.int32)
    at = 0
    for i, p in enumerate(parts):
        out[at:at + p.shape[0]] = p
        at += p.shape[0]
        parts[i] = None  # release as we go
    return out


def _assemble(g: Graph, r: int, s: int, r_rows: np.ndarray,
              s_rows: np.ndarray, orientation: str,
              budget: int, stats: Dict[str, Any]) -> NucleusProblem:
    """Shared incidence assembly from host-resident clique rows.

    The sort-join and CSR fill are blocked by ``budget``; every step is a
    per-row pure function of the eager path's, so output is bit-identical.
    """
    with span("build.assemble", n_r=int(r_rows.shape[0]),
              n_s=int(s_rows.shape[0])):
        r_table, inc, mem_offsets, mem_sids, deg0 = _assemble_host(
            r, s, r_rows, s_rows, budget, stats)
    with span("build.upload"):
        return NucleusProblem(
            g=g, r=r, s=s, r_cliques=jnp.asarray(r_table),
            inc_rid=jnp.asarray(inc), mem_offsets=jnp.asarray(mem_offsets),
            mem_sids=jnp.asarray(mem_sids), deg0=jnp.asarray(deg0),
            orientation=orientation, build_stats=stats)


def _assemble_host(r: int, s: int, r_rows: np.ndarray, s_rows: np.ndarray,
                   budget: int, stats: Dict[str, Any]):
    """``_assemble``'s numpy part: (r_table, inc, mem_offsets, mem_sids,
    deg0) as host arrays."""
    C = comb(s, r)
    n_s = int(s_rows.shape[0])
    if r_rows.shape[0]:
        order = np.lexsort(tuple(r_rows[:, c] for c in reversed(range(r))))
        r_table = r_rows[order]
    else:
        r_table = r_rows.reshape(0, r)
    n_r = int(r_table.shape[0])

    # blocked sort-join: ids are a per-query-row function of (table, row),
    # so block boundaries cannot change them
    q_block = max(1, int(budget // max(8 * 4 * C * max(r, 1), 1)))
    inc = np.empty((n_s, C), np.int32)
    join_bytes = 0
    for b0 in range(0, n_s, q_block):
        blk = s_rows[b0:b0 + q_block]
        qs = np.concatenate([blk[:, list(cols)]
                             for cols in subset_columns(s, r)], axis=0)
        join_bytes = max(join_bytes, 3 * int(qs.nbytes))
        ids = sort_join_np(r_table, qs)
        inc[b0:b0 + blk.shape[0]] = np.stack(np.split(ids, C), axis=1)

    # two-pass mem-CSR: counts (= deg0) first, then a cursor fill that
    # reproduces the stable argsort grouping of csr_from_pairs
    deg0 = np.bincount(inc.reshape(-1), minlength=n_r).astype(np.int32) \
        if n_s else np.zeros((n_r,), np.int32)
    mem_offsets = np.concatenate(
        [np.zeros((1,), np.int32),
         np.cumsum(deg0, dtype=np.int64).astype(np.int32)])
    mem_sids = np.empty((n_s * C,), np.int32)
    cursor = mem_offsets[:-1].astype(np.int64)
    for b0 in range(0, n_s, q_block):
        blk = inc[b0:b0 + q_block]
        rid = blk.reshape(-1)
        sid = np.repeat(np.arange(b0, b0 + blk.shape[0], dtype=np.int32), C)
        ordr = np.argsort(rid, kind="stable")
        rid_s, sid_s = rid[ordr], sid[ordr]
        uniq, counts = np.unique(rid_s, return_counts=True)
        run_starts = np.cumsum(counts) - counts
        occ = np.arange(rid_s.size, dtype=np.int64) - \
            np.repeat(run_starts, counts)
        mem_sids[cursor[rid_s] + occ] = sid_s
        cursor[uniq] += counts

    stats["peak_intermediate_bytes"] = max(
        stats.get("peak_intermediate_bytes", 0), join_bytes)
    return r_table, inc, mem_offsets, mem_sids, deg0


def _oriented_counts(dense: jnp.ndarray) -> jnp.ndarray:
    """(D @ Dᵀ) ⊙ D for the (2,3) count pass: the Pallas boolean-tile kernel
    on accelerators, the pure-jnp oracle on CPU (interpret-mode Pallas walks
    the tile grid in Python — one XLA matmul is the honest CPU path).  A
    kernel failure on an accelerator propagates."""
    import jax
    from ..kernels import ops, ref
    if jax.default_backend() == "cpu":
        return ref.tricount_oriented_ref(dense)
    return ops.tricount_oriented(dense)


def _fastpath_ok(r: int, s: int, dg: Digraph, budget: int) -> bool:
    """Dense (2,3) count pass: the count stage holds ~4 (n, n) f32 blocks
    live (np dense, its jnp copy, the jnp counts, their np copy) plus one
    edge-block of membership rows — all must fit the budget."""
    return (r, s) == (2, 3) and 5 * dg.n * dg.n * 4 <= budget


def _build_chunked(g: Graph, r: int, s: int, dg: Digraph, orientation: str,
                   memory_budget_bytes: Optional[int],
                   chunk_size: Optional[int],
                   fastpath: Optional[bool]) -> NucleusProblem:
    budget = memory_budget_bytes if memory_budget_bytes is not None \
        else DEFAULT_BUILD_BUDGET
    if fastpath and (r, s) != (2, 3):
        raise ValueError(
            f"fastpath=True is the dense (2,3) count pass; it does not "
            f"apply to (r, s) = ({r}, {s})")
    # an explicit chunk_size pins the sparse seed-chunked path (the caller
    # is asking for a specific chunking, e.g. the equivalence tests)
    use_fast = (_fastpath_ok(r, s, dg, budget) and chunk_size is None) \
        if fastpath is None else bool(fastpath)
    if use_fast and (r, s) == (2, 3):
        return _build_chunked_23_dense(g, dg, orientation, budget)

    chunk = chunk_size if chunk_size is not None \
        else _derive_chunk_size(dg, s, budget)
    r_parts: List[np.ndarray] = []
    s_parts: List[np.ndarray] = []
    peak = 0
    n_chunks = 0
    for _start, levels, chunk_peak in iter_clique_chunks(dg, [r, s], chunk):
        n_chunks += 1
        peak = max(peak, int(chunk_peak))
        r_parts.append(np.asarray(levels[r]))
        s_parts.append(np.asarray(levels[s]))
    r_rows = _fill_parts(r_parts, r)
    s_rows = _fill_parts(s_parts, s)
    stats = {"build": "chunked", "chunk_size": chunk, "n_chunks": n_chunks,
             "peak_intermediate_bytes": peak,
             "memory_budget_bytes": memory_budget_bytes, "fastpath": False}
    return _assemble(g, r, s, r_rows, s_rows, orientation, budget, stats)


def _build_chunked_23_dense(g: Graph, dg: Digraph, orientation: str,
                            budget: int) -> NucleusProblem:
    """(2,3) fast path: Pallas boolean-tile count pass + dense-row fill.

    Pass 1 (count) runs ``tricount_oriented`` — (D @ Dᵀ) ⊙ D on the oriented
    0/1 block — so per-edge triangle-extension counts, and therefore every
    allocation size, come off the MXU without materializing a candidate
    array.  Pass 2 (fill) walks DAG edges in CSR order in budget-sized
    blocks; each block's candidate intersections are dense row products,
    and nonzero extraction emits triangles in exactly the expansion order
    of the sparse builder (u-major, then v, then w ascending), so output is
    bit-identical.  Falls back to the pure-jnp oracle when the Pallas call
    is unavailable.
    """
    n = dg.n
    with span("build.count", n=n):
        outdeg = np.asarray(dg.outdeg)
        nbrs = np.asarray(dg.neighbors)
        src = np.repeat(np.arange(n, dtype=np.int32), outdeg)
        dense = np.zeros((n, n), np.float32)
        if src.size:
            dense[src, nbrs] = 1.0
        counts_nn = np.asarray(_oriented_counts(jnp.asarray(dense)))
        ext = counts_nn[src, nbrs].astype(np.int64) if src.size \
            else np.zeros((0,), np.int64)
        n_s = int(ext.sum())

    # r-cliques = DAG edges in CSR (expansion) order, rows ascending
    r_rows = np.sort(np.stack([src, nbrs], axis=1), axis=1).astype(np.int32) \
        if src.size else np.zeros((0, 2), np.int32)

    # fill pass: membership rows for a block of edges at a time
    e_block = max(1, int(budget // max(3 * 4 * n, 1)))
    s_rows = np.empty((n_s, 3), np.int32)
    at = 0
    n_blocks = 0
    with span("build.fill", n_s=n_s):
        for e0 in range(0, src.size, e_block):
            u = src[e0:e0 + e_block]
            v = nbrs[e0:e0 + e_block]
            members = dense[u] * dense[v]  # (block, n) common out-neighbors
            eidx, w = np.nonzero(members)  # row-major: edge order, w asc.
            tri = np.stack([u[eidx].astype(np.int32),
                            v[eidx].astype(np.int32),
                            w.astype(np.int32)], axis=1)
            tri.sort(axis=1)
            s_rows[at:at + tri.shape[0]] = tri
            at += tri.shape[0]
            n_blocks += 1
    assert at == n_s, (at, n_s)  # kernel counts must agree with the fill

    # the count stage held ~4 (n, n) f32 blocks live (np dense + jnp copy +
    # jnp counts + np counts); the fill holds 3 edge-blocks (u/v gathers +
    # their product) on top of dense
    peak = 4 * dense.nbytes + 3 * e_block * n * 4
    stats = {"build": "chunked", "chunk_size": e_block, "n_chunks": n_blocks,
             "peak_intermediate_bytes": int(peak),
             "memory_budget_bytes": budget, "fastpath": True}
    return _assemble(g, 2, 3, r_rows, s_rows, orientation, budget, stats)
