"""Distributed (r, s) nucleus decomposition under `jax.shard_map`.

A thin wrapper over the unified peel engine (``repro.core.engine``): the
s-clique incidence structure is partitioned across devices (each device owns
a contiguous slab of s-cliques); r-clique degree/peeled state is replicated.
The shared ``peel_round`` body runs per shard with its ``reduce_delta`` hook
bound to one psum of the (n_r,) int32 decrement vector — the distributed
analogue of the paper's atomic decrements.  The whole loop is the engine's
`lax.while_loop` with fixed shapes, so it jits, lowers and compiles for any
mesh (this is what the multi-pod dry-run exercises).

Both exact and approximate (Alg. 2) bucket schedules are supported via the
same ``PeelSchedule`` every backend uses; the approximate schedule's
geometric thresholds make the trip count O(log^2 n), which is the paper's
span result translated to "number of all-reduces".
"""
from __future__ import annotations

import functools
from math import comb
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..graph import INT
from .engine import run_peel_engine
from .incidence import NucleusProblem
from .schedule import PeelSchedule


def _varying(x, axis_names):
    """Mark x device-varying for shard_map's varying-manual-axes typing."""
    return jax.lax.pcast(x, axis_names, to="varying")


def pad_incidence(inc_rid: jnp.ndarray, n_shards: int):
    """Pad the s-clique axis to a multiple of the shard count.

    Padded rows point at a ghost r-clique id (-1) whose updates are dropped.
    """
    n_s, C = inc_rid.shape
    pad = (-n_s) % n_shards
    if pad:
        ghost = jnp.full((pad, C), -1, INT)
        inc_rid = jnp.concatenate([inc_rid, ghost], axis=0)
    return inc_rid, n_s + pad


def sharded_incidence(problem: NucleusProblem, mesh: Mesh) -> jnp.ndarray:
    """The problem's incidence padded to a multiple of the mesh's devices
    and laid out as the sharded peel reads it: contiguous s-clique slabs,
    one per device (axis 0 over every mesh axis).

    Memoized on the problem per mesh, like the dense engine's scatter
    plans: repeated sharded runs skip the pad and the host-to-device
    copy, and a caller can inspect the very array the run used
    (``addressable_shards``)."""
    cache = getattr(problem, "_sharded_inc", None)
    if cache is None:
        cache = {}
        problem._sharded_inc = cache
    if mesh not in cache:
        n_dev = int(np.prod(mesh.devices.shape))
        inc, _ = pad_incidence(problem.inc_rid, n_dev)
        cache[mesh] = jax.device_put(
            inc, NamedSharding(mesh, P(tuple(mesh.axis_names))))
    return cache[mesh]


def make_sharded_decomposition(mesh: Mesh, n_r: int, n_s_padded: int, C: int,
                               schedule: PeelSchedule,
                               max_rounds: Optional[int] = None,
                               compress: bool = False,
                               hierarchy: bool = False,
                               padded: bool = False):
    """Build the jittable distributed decomposition for a mesh.

    Returns (fn, in_shardings, out_shardings); fn(inc_rid, deg0) -> (core,
    rounds) — or (core, rounds, parent, L) with hierarchy=True.  inc_rid is
    sharded over all mesh axes (s-clique partition), state is replicated.

    padded=True is the shape-bucketed variant (``core.session``): fn takes
    a third replicated ``peeled0`` bool mask marking ghost r-cliques of a
    padded shape class as pre-peeled (they never bucket, emit no links,
    keep core/order at -1).  The default 2-arg signature is unchanged —
    the multi-pod dry-run lowers it as-is.

    compress=True: the (n_r,) int32 delta all-reduce is sent as int16 with
    per-shard saturation + ERROR FEEDBACK — the saturated remainder stays in
    a local residual and is re-sent next round.  Degrees therefore lag by at
    most a round for pathological hubs but never undershoot, and every
    destroyed incidence is eventually counted exactly (peel levels are
    monotone, so late decrements only delay a peel, never mis-assign a
    core).  Halves the per-round collective bytes (the dominant term).

    hierarchy=True fuses the ANH-EL LINK state into the same loop: each
    round's links are generated from the device-local s-clique slab
    (``engine.round_links``; ghost rows emit nothing, last_peeled stays
    device-local), all-gathered so every device sees the round's global
    link multiset, and folded into the REPLICATED (parent, L) carry by the
    same ``engine.link_fixpoint`` the dense backend runs — value-identical
    on every device, so the emitted forest equals the single-device fused
    forest exactly.
    """
    n_dev = int(np.prod(mesh.devices.shape))
    if n_s_padded % n_dev:
        # pow2 bucketing alone is NOT shard-aware: a mesh whose device
        # count is not a power of two would slice the s-clique axis
        # raggedly and shard_map rejects (or worse, silently uneven-pads)
        # the operand.  Callers pad via pad_incidence or round the bucket
        # with session.shard_bucket_size.
        raise ValueError(
            f"n_s_padded={n_s_padded} is not a multiple of the mesh's "
            f"{n_dev} devices — the shard_map s-clique slices would be "
            f"ragged; pad with pad_incidence() or round the shape class "
            f"with session.shard_bucket_size()")
    axis_names = tuple(mesh.axis_names)
    shard_spec = P(axis_names)      # all axes partition the s-clique dim
    repl_spec = P()
    cap_rounds = max_rounds if max_rounds is not None else n_r + 2

    def reduce_delta(delta, resid):
        if compress:
            delta = delta + resid
            sent = jnp.minimum(delta, 32767).astype(jnp.int16)
            resid = delta - sent.astype(INT)
            red = sent
            for ax in axis_names:
                red = jax.lax.psum(red, ax)  # s16 on the wire: half the bytes
            return red.astype(INT), resid
        for ax in axis_names:
            delta = jax.lax.psum(delta, ax)
        return delta, resid

    def gather_links(la, lb, lv):
        for ax in axis_names:
            la = jax.lax.all_gather(la, ax, tiled=True)
            lb = jax.lax.all_gather(lb, ax, tiled=True)
            lv = jax.lax.all_gather(lv, ax, tiled=True)
        return la, lb, lv

    def replicate(x):
        # parent/L are value-identical across devices (every device folded
        # the same gathered multiset); pmax is an identity that re-types
        # them replicated so out_specs=P() checks under VMA tracking
        for ax in axis_names:
            x = jax.lax.pmax(x, ax)
        return x

    def local_fn(inc_local, deg0, peeled0=None):
        # alive/residual are per-shard state: mark them device-varying so
        # the engine's while_loop carry types match (shard_map VMA tracking)
        n_s_local = inc_local.shape[0]
        alive0 = _varying(jnp.ones((n_s_local,), bool), axis_names)
        resid0 = _varying(
            jnp.zeros((n_r,) if compress else (1,), INT), axis_names)
        if hierarchy:
            link0 = (_varying(jnp.arange(n_r, dtype=INT), axis_names),
                     _varying(jnp.full((n_r,), -1, INT), axis_names),
                     _varying(jnp.full((n_s_local,), -1, INT), axis_names))
            core, _order, rounds, _counts, parent, L = run_peel_engine(
                inc_local, deg0, schedule, max_rounds=cap_rounds,
                reduce_delta=reduce_delta, resid0=resid0, alive0=alive0,
                hierarchy=True, link0=link0, gather_links=gather_links,
                peeled0=peeled0)
            return core, rounds, replicate(parent), replicate(L)
        core, _order, rounds, _counts = run_peel_engine(
            inc_local, deg0, schedule, max_rounds=cap_rounds,
            reduce_delta=reduce_delta, resid0=resid0, alive0=alive0,
            peeled0=peeled0)
        return core, rounds

    n_out = 4 if hierarchy else 2
    n_in = 3 if padded else 2
    if not padded:
        # keep the historical 2-arg signature: the dry-run lowers it
        body = lambda inc_local, deg0: local_fn(inc_local, deg0)
    else:
        body = local_fn
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(shard_spec,) + (repl_spec,) * (n_in - 1),
                       out_specs=(repl_spec,) * n_out)
    in_sh = (NamedSharding(mesh, shard_spec),) + \
        (NamedSharding(mesh, repl_spec),) * (n_in - 1)
    out_sh = (NamedSharding(mesh, repl_spec),) * n_out
    return fn, in_sh, out_sh


@functools.lru_cache(maxsize=64)
def _jitted_decomposition(mesh: Mesh, n_r: int, n_s_padded: int, C: int,
                          schedule: PeelSchedule,
                          max_rounds: Optional[int], compress: bool,
                          hierarchy: bool, padded: bool = False):
    """Warm pool for the sharded fn: ``jax.jit`` caches executables per
    *callable object*, and ``make_sharded_decomposition`` used to return a
    fresh closure on every call — so every sharded run recompiled even for
    identical shapes.  Memoizing the jitted callable on the hashable key
    (Mesh compares by value) makes repeated same-shape sharded runs reuse
    the compiled executable — the warm-pool behaviour ``core.session``
    relies on."""
    fn, _, _ = make_sharded_decomposition(mesh, n_r, n_s_padded, C, schedule,
                                          max_rounds, compress=compress,
                                          hierarchy=hierarchy, padded=padded)
    return jax.jit(fn)


def sharded_decomposition_padded(inc: jnp.ndarray, deg0: jnp.ndarray,
                                 peeled0: jnp.ndarray, mesh: Mesh,
                                 schedule: PeelSchedule, *,
                                 max_rounds: Optional[int] = None,
                                 compress: bool = False,
                                 hierarchy: bool = False):
    """Run the sharded peel on an already shape-bucketed problem.

    ``core.session``'s sharded warm path: the caller has padded the
    s-clique axis to a shard-multiple shape class (``shard_bucket_size``)
    with ghost -1 rows, the r-clique axis to its bucket with ghost
    pre-peeled entries (``peeled0``), and canonicalized the schedule — so
    same-bucket problems key the same ``_jitted_decomposition`` entry and
    reuse one shard_map executable.  Returns the engine outputs unsliced
    (the caller trims the ghost tail)."""
    n_s_pad, C = int(inc.shape[0]), int(inc.shape[1])
    fn = _jitted_decomposition(mesh, int(deg0.shape[0]), n_s_pad, C,
                               schedule, max_rounds, compress, hierarchy,
                               padded=True)
    return fn(inc, deg0, peeled0)


def sharded_decomposition(problem: NucleusProblem, mesh: Mesh,
                          kind: str = "exact", delta: float = 0.1,
                          max_rounds: Optional[int] = None,
                          compress: bool = False, hierarchy: bool = False):
    """Run the distributed decomposition end-to-end on real data.

    Returns (core, rounds); with hierarchy=True, (core, rounds, parent, L,
    peel_value) — the fused ANH-EL join forest, identical to the
    single-device fused forest, plus the raw (unclipped) peel values it was
    built over: ``link_state_from_forest(peel_value, parent, L)`` is the
    tree-building input, NOT the clipped approx estimates in ``core``.
    """
    inc = sharded_incidence(problem, mesh)
    schedule = PeelSchedule(kind=kind, s_choose_r=comb(problem.s, problem.r),
                            delta=delta, n=problem.g.n)
    fn = _jitted_decomposition(mesh, problem.n_r, int(inc.shape[0]),
                               problem.n_sub, schedule, max_rounds,
                               compress, hierarchy)
    out = fn(inc, problem.deg0)
    core, rounds = out[0], out[1]
    raw = core
    if kind == "approx":  # practical tightening (paper §6)
        core = jnp.minimum(core, problem.deg0)
    if hierarchy:
        return core, int(rounds), out[2], out[3], raw
    return core, int(rounds)


def dryrun_specs(n_r: int, n_s: int, C: int):
    """ShapeDtypeStructs for lowering the decomposition without data."""
    return (jax.ShapeDtypeStruct((n_s, C), jnp.int32),
            jax.ShapeDtypeStruct((n_r,), jnp.int32))
