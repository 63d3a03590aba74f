"""Connected components via min-hooking + pointer jumping.

Data-parallel replacement for both the paper's linear-work connectivity [22]
and the Jayanti–Tarjan concurrent union-find: every round scatter-mins the
smaller endpoint label over each edge, then pointer-jumps labels to their
fixpoint.  Deterministic, O(log n) rounds w.h.p. on real graphs, each round a
fixed pattern of gathers/scatters (the shape TPUs execute well).  We trade the
paper's O(m) work for O(m log n); DESIGN.md records the trade.

Both loops are fixed-carry ``lax.while_loop``s: no ``bool(...)`` host sync per
round, so they trace under ``jit`` / ``shard_map`` and the fused hierarchy
engine (``repro.core.engine``) can nest them inside its peel loop.  Eager
callers get the same device-resident loop (one dispatch per call).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .container import INT


def _varying(x, *like):
    """``x`` cast to the union of the varying manual axes of ``like``.

    Under ``shard_map`` a ``while_loop`` carry must keep its type across
    the body; a flag or label array born replicated but updated from
    device-varying operands would change type, so the initial carry is
    cast up front.  Outside ``shard_map`` there are no such axes and
    ``x`` passes through."""
    axes = set()
    for y in like:
        axes |= set(jax.typeof(y).vma)
    axes -= set(jax.typeof(x).vma)
    if not axes:
        return x
    return jax.lax.pcast(x, tuple(sorted(axes)), to="varying")


def pointer_jump(labels: jnp.ndarray, iters: int | None = None) -> jnp.ndarray:
    """Resolve label forest to roots: labels <- labels[labels] to fixpoint.

    Pointer doubling squares path lengths each step, so the default cap of
    n+1 iterations is never binding (depth halves per step); `iters` bounds
    the trip count explicitly when the caller knows the depth.
    """
    n = int(labels.shape[0])
    if n == 0:
        return labels
    cap = iters if iters is not None else n + 1

    # the l[l] gather lives in body only (this is the innermost loop of the
    # fused engine's 4-deep nest; a gather in cond would double it)
    def cond(carry):
        _, changed, i = carry
        return changed & (i < cap)

    def body(carry):
        l, _, i = carry
        nxt = l[l]
        return nxt, jnp.any(nxt != l), i + 1

    out, _, _ = jax.lax.while_loop(
        cond, body, (labels, _varying(jnp.asarray(True), labels),
                     jnp.zeros((), INT)))
    return out


def connected_components(n: int, u: jnp.ndarray, v: jnp.ndarray,
                         init: jnp.ndarray | None = None) -> jnp.ndarray:
    """Component labels (min vertex id reachable) for graph (n, edges u-v).

    `init` seeds labels (e.g. an existing union-find forest, resolved or
    not); self-edges are no-ops, so callers with fixed-shape edge buffers can
    mask invalid slots to (0, 0).  Returned labels are fully resolved
    (labels[labels] == labels).
    """
    labels = (jnp.arange(n, dtype=INT) if init is None
              else pointer_jump(init.astype(INT)))
    if int(u.shape[0]) == 0 or n == 0:
        return labels

    def hook(l):
        lu, lv = l[u], l[v]
        m = jnp.minimum(lu, lv)
        # Hook at the ROOTS (lu, lv), not the endpoints: hooking endpoints
        # only relabels vertices incident to the current edge set, which
        # fractures components seeded via `init` whose members are not
        # endpoints.  Root-hooking + jumping converges for both cases.
        return pointer_jump(l.at[lu].min(m).at[lv].min(m))

    def cond(carry):
        _, changed = carry
        return changed

    def body(carry):
        l, _ = carry
        new = hook(l)
        return new, jnp.any(new != l)

    labels = _varying(labels, u, v)
    labels, _ = jax.lax.while_loop(
        cond, body, (labels, _varying(jnp.asarray(True), labels)))
    return labels
