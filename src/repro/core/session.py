"""Warm ``Session``: decompose many graphs without recompiling per shape.

``decompose()`` is one-shot: every new problem shape keys a fresh XLA
compile of the dense engine (shapes + the static ``PeelSchedule`` make up
the executable cache key), so a serving process that decomposes a stream
of similar graphs pays the dominant cost — compilation — over and over.
``Session`` is the warm-pool front door:

  * **Shape buckets.**  Each problem is padded to a shape class
    (``n_r``/``n_s`` rounded up to the next power of two, floor
    ``bucket_floor``): ghost s-clique rows carry ``-1`` member ids (the
    engine's distributed padding convention — they die in round 0 and
    contribute no decrements, no links) and ghost r-cliques enter
    pre-peeled (``peeled0``), so they never join a bucket, never drag the
    schedule minimum, and keep core/order at -1.  The real prefix of every
    output is bit-identical to the unpadded run (tests pin this
    array-for-array against ``decompose()``).
  * **Schedule canonicalization.**  The static ``PeelSchedule`` carries
    the vertex count ``n``, which differs per graph and would defeat the
    bucket.  Exact schedules never read ``n`` (pinned to 1); approximate
    schedules read it only through ``cap()``, so ``n`` is replaced by the
    smallest vertex count with the same cap — same compiled behaviour,
    same results, one executable per (delta, C, cap) class.
  * **Warm executables.**  With shapes and statics canonicalized,
    same-bucket problems hit the engine's jitted-callable cache instead of
    recompiling; ``Session.stats`` records the bucket hit pattern, and the
    ``session`` bench lane + EXPERIMENTS.md record the cold/warm speedup.

Pallas configs ride the warm path too: the scatter plan (the rid-sorted
CSR edge arrays of ``engine._scatter_plan``) is padded to the same pow2
buckets (edge axis bucketed at floor ``chunk_e``, chunk-span bound
pow2-rounded), so ``use_pallas=True`` — or a profile that defaults it on,
as on a TPU — reuses one executable per shape class instead of
recompiling per problem.  Sharded configs ride it as well: the s-clique
axis is padded to SHARD-MULTIPLE shape classes (``shard_bucket_size`` —
pow2 alone slices raggedly when the mesh size is not a power of two;
DESIGN.md §13) with ghost -1 rows, ghost r-cliques enter pre-peeled, and
same-bucket problems reuse one ``shard_map`` executable through
``distributed._jitted_decomposition``.  Configs that resolve to any other
non-dense backend, or whose padded plan would exceed
``PLAN_BUDGET_BYTES``, fall back to the planned cold path (same ``Plan``
provenance, counted in ``stats["fallback"]``): correct, just not
bucket-warmed.
``launch.serve --arch nucleus --warm-pool`` drives this end-to-end.
"""
from __future__ import annotations

import dataclasses
import threading
from math import comb
from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..graph import INT
from ..kernels.segment_sum import DEFAULT_BLOCK_N, DEFAULT_CHUNK_E
from .api import (Decomposition, NucleusConfig, execute_plan, plan_config,
                  resolve_problem)
from .engine import (ScatterSpec, _scatter_plan, dense_coreness,
                     pallas_by_default, round_up_pow2_chunks)
from .incidence import NucleusProblem
from .schedule import PeelSchedule
from .trace import span

DEFAULT_BUCKET_FLOOR = 64
# ceiling on ``padded_plan_bytes`` for the bucketed path and for serving
# admission; a problem over it takes the cold path
PLAN_BUDGET_BYTES = 1 << 29
# default LRU bound on stats["buckets"]: generous for real serving mixes
# (hundreds of shape classes) while keeping a long-lived process O(1)
DEFAULT_BUCKET_CAP = 256

# the session-manifest wire format (serve.cache persists it so a restarted
# server can pre-warm the same shape buckets before taking traffic)
MANIFEST_FORMAT = "repro.session-manifest"
MANIFEST_VERSION = 1


def bucket_size(n: int, floor: int = DEFAULT_BUCKET_FLOOR) -> int:
    """Next power of two >= max(n, floor): the shape-class boundary.

    Power-of-two classes bound the padding overhead at 2x work per axis
    while collapsing the long tail of near-miss shapes onto one compiled
    executable."""
    n = max(int(n), int(floor), 1)
    return 1 << (n - 1).bit_length()


def padded_plan_bytes(problem: NucleusProblem) -> int:
    """The size estimate the bucketed path and serving admission are
    gated on: 4 * e_pad * C bytes, an (e_pad, C) int32 member matrix with
    the edge axis pow2-bucketed.  The scatter plan itself is two (e_pad,)
    int32 arrays; the estimate stays at the size the warm path was sized
    and tested at, since the engine's own device memory is many times
    the plan's (PERF.md) and a higher admission ceiling needs that
    measured at the larger buckets first."""
    e_pad = bucket_size(problem.n_s * problem.n_sub, DEFAULT_CHUNK_E)
    return 4 * e_pad * problem.n_sub


def shard_bucket_size(n: int, n_shards: int,
                      floor: int = DEFAULT_BUCKET_FLOOR) -> int:
    """Shard-aware shape class: the pow2 bucket rounded UP to a multiple
    of ``n_shards``.

    ``shard_map`` slices the s-clique axis evenly across the mesh, so a
    sharded bucket must be a shard multiple — pow2 alone is ragged
    whenever the device count is not a power of two (the PR-5 leftover;
    ``make_sharded_decomposition`` rejects ragged shapes).  For pow2
    shard counts <= the bucket this is the identity, so near-miss shapes
    still collapse onto one shard_map executable."""
    b = bucket_size(n, floor)
    n_shards = max(int(n_shards), 1)
    return -(-b // n_shards) * n_shards


def canonical_schedule(method: str, s_choose_r: int, delta: float,
                       n: int) -> PeelSchedule:
    """The behaviour-preserving schedule representative of (method, C,
    delta, n)'s equivalence class.

    Exact schedules never read ``n`` or ``delta``; approximate schedules
    read ``n`` only through ``cap()`` (the per-bucket round cap), so the
    smallest ``n`` with the same cap is substituted (binary search — cap
    is nondecreasing in n).  Results are bit-identical to the
    uncanonicalized schedule; the static jit key stops varying per graph.
    """
    if method == "exact":
        return PeelSchedule(kind="exact", s_choose_r=s_choose_r)
    mk = lambda nn: PeelSchedule(kind="approx", s_choose_r=s_choose_r,
                                 delta=delta, n=nn)
    target = mk(n).cap()
    lo, hi = 2, max(int(n), 2)
    while lo < hi:
        mid = (lo + hi) // 2
        if mk(mid).cap() >= target:
            hi = mid
        else:
            lo = mid + 1
    return mk(lo)


@dataclasses.dataclass(frozen=True)
class _Bucket:
    """One shape class: the statics + padded shapes a compiled executable
    keys on.  ``astuple`` is the hashable stats key."""

    method: str
    r: int
    s: int
    fused: bool
    n_r_pad: int
    n_s_pad: int
    schedule: PeelSchedule
    # the Pallas scatter tiling this bucket compiles with (None = pure
    # XLA round body); the plan arrays are padded to the same pow2 buckets
    # (edge axis included) so warm members reuse the executable
    pallas: Optional[ScatterSpec] = None
    # mesh device count of a sharded bucket (0 = single-device dense);
    # its n_s_pad is a shard multiple (``shard_bucket_size``).  NEW FIELDS
    # GO AFTER THIS ONE: positional consumers (router report, manifest
    # ``_Bucket(*key)``) index the prefix.
    shards: int = 0

    def astuple(self) -> Tuple:
        return (self.method, self.r, self.s, self.fused, self.n_r_pad,
                self.n_s_pad, self.schedule, self.pallas, self.shards)


@dataclasses.dataclass(frozen=True)
class _PaddedProblem:
    """The minimal view ``dense_coreness`` reads off a problem (the mem-CSR
    and r-clique table stay on the real problem — queries never see the
    padding)."""

    inc_rid: jnp.ndarray
    deg0: jnp.ndarray
    n_r: int
    n_s: int


class Session:
    """Warm decompose-many: ``Session(config).decompose(graph)``.

    The config is fixed at construction (keyword overrides apply on top,
    like ``decompose``); every ``decompose``/``decompose_many`` call runs
    the same pipeline as the module-level ``decompose()`` — same planner,
    same validation, same ``Decomposition`` artifact — but routes dense
    peels through the shape-bucketed padded engine so same-bucket problems
    reuse one compiled executable.  ``stats`` tallies warm vs cold engine
    calls and the per-bucket hit counts.
    """

    def __init__(self, config: Optional[NucleusConfig] = None, *,
                 bucket_floor: int = DEFAULT_BUCKET_FLOOR,
                 bucket_cap: int = DEFAULT_BUCKET_CAP, **overrides):
        if config is None:
            config = NucleusConfig()
        if overrides:
            config = dataclasses.replace(config, **overrides)
        config.validate()
        self.config = config
        self.bucket_floor = int(bucket_floor)
        # bound on tracked shape classes: a long-lived serving process
        # seeing adversarial shape streams must not grow bookkeeping
        # without limit (ROADMAP's PR-5 leftover).  0 disables the cap.
        self.bucket_cap = int(bucket_cap)
        self.stats: Dict[str, Any] = {
            "decompositions": 0,   # total artifacts produced
            "warm": 0,             # padded engine calls that hit a bucket
            "cold": 0,             # padded engine calls compiling a bucket
            "fallback": 0,         # routed to plain decompose()
            "updates": 0,          # incremental update() calls served
            "stream_warm": 0,      # update stages hitting a known bucket
            "stream_cold": 0,      # update stages opening a bucket
            "evictions": 0,        # bucket entries dropped by the LRU cap
            "prewarmed": 0,        # buckets compiled ahead of traffic
            "buckets": {},         # bucket key -> call count (LRU order)
        }
        # counter + bucket-table mutations take this lock so concurrent
        # readers (a status endpoint polling while the serving worker
        # decomposes) never see torn LRU state and no increment is lost;
        # the ENGINE path stays single-writer by frontend discipline —
        # the lock protects bookkeeping, not compiled-call ordering
        self._stats_lock = threading.Lock()
        # decompose-bucket extras the manifest needs but the hashable key
        # cannot carry (the padded plan-array length of Pallas buckets)
        self._bucket_meta: Dict[Tuple, Dict[str, Any]] = {}

    # -- front door --------------------------------------------------------
    def decompose(self, graph_or_problem) -> Decomposition:
        """Same contract (and bit-identical arrays) as
        ``api.decompose(graph_or_problem, self.config)``."""
        with span("session.decompose") as sp:
            problem, config = resolve_problem(graph_or_problem, self.config)
            sp.add(n_r=problem.n_r, n_s=problem.n_s)
            return self._decompose(problem, config)

    def _decompose(self, problem: NucleusProblem,
                   config: NucleusConfig) -> Decomposition:
        config, plan = plan_config(problem, config)
        self._count("decompositions")
        # the padded path covers the compiled dense engine, XLA round body
        # AND Pallas scatter plan: the plan is padded to the same pow2
        # buckets (edge axis included), so use_pallas rides the warm path
        # too.  Only a plan over PLAN_BUDGET_BYTES takes the cold path.
        wants_pallas = bool(config.use_pallas or (
            config.use_pallas is None and pallas_by_default()))
        # gate on what the padded plan actually allocates — a problem just
        # under budget unpadded can land over it padded
        plan_bytes = padded_plan_bytes(problem)
        if config.backend == "sharded" and problem.n_r > 0:
            return self._decompose_padded_sharded(problem, config, plan)
        if config.backend != "dense" or problem.n_r == 0 or (
                wants_pallas and plan_bytes > PLAN_BUDGET_BYTES):
            self._count("fallback")
            return execute_plan(problem, config, plan)
        return self._decompose_padded(problem, config, plan,
                                      wants_pallas=wants_pallas)

    def decompose_many(self, graphs) -> List[Decomposition]:
        """Decompose a stream; same-bucket members after the first are
        warm.  Order of results matches the input order."""
        return [self.decompose(g) for g in graphs]

    def update(self, dec: Decomposition, delta) -> Decomposition:
        """Incrementally patch ``dec`` (same parity contract as
        ``Decomposition.update``) while bucketing the streaming engine's
        compiled stages.

        The local converge / link-fixpoint stages are jitted on
        pow2-padded shapes, so repeat updates against a live graph land
        in the same shape classes; their keys join ``stats['buckets']``
        (and the LRU cap) alongside the decompose buckets, tallied as
        ``stream_warm`` / ``stream_cold``."""
        self._count("updates")

        def hook(key: Tuple) -> None:
            warm = self._bucket_hit(key)
            self._count("stream_warm" if warm else "stream_cold")

        return dec.update(delta, bucket_hook=hook)

    # -- the padded dense path ---------------------------------------------
    def _bucket(self, problem: NucleusProblem, config: NucleusConfig, *,
                wants_pallas: Optional[bool] = None) -> "_Bucket":
        """The shape class ``problem`` lands in under ``config``: the
        canonical schedule plus padded shapes (everything the compiled
        executable depends on), computed once and named."""
        if wants_pallas is None:
            wants_pallas = bool(config.use_pallas or (
                config.use_pallas is None and pallas_by_default()))
        n_r_pad = bucket_size(problem.n_r, self.bucket_floor)
        pallas_spec = None
        if wants_pallas and problem.n_s > 0:
            pallas_spec = self._pallas_spec(problem, n_r_pad)
        return _Bucket(
            method=config.method, r=config.r, s=config.s,
            fused=config.hierarchy == "fused",
            n_r_pad=n_r_pad,
            n_s_pad=bucket_size(problem.n_s, self.bucket_floor),
            schedule=canonical_schedule(config.method, problem.n_sub,
                                        config.delta, problem.g.n),
            pallas=pallas_spec)

    def _pallas_plan(self, problem: NucleusProblem, n_r_pad: int):
        """The bucketed scatter plan: CSR edge arrays padded to pow2
        shape classes (edge count included, floor ``chunk_e``) with a
        pow2-rounded chunk-span bound, so the ScatterSpec — part of the
        executable's jit key — repeats across same-bucket problems."""
        block_n, chunk_e = DEFAULT_BLOCK_N, DEFAULT_CHUNK_E
        e_real = int(problem.mem_sids.shape[0])
        e_pad = bucket_size(e_real, chunk_e)
        n_seg_pad = max(n_r_pad, block_n)
        return _scatter_plan(problem, block_n, chunk_e,
                             jax.default_backend() == "cpu",
                             e_pad=e_pad, n_r_pad=n_seg_pad,
                             pow2_chunks=True)

    def _pallas_spec(self, problem: NucleusProblem,
                     n_r_pad: int) -> ScatterSpec:
        """``_pallas_plan``'s ScatterSpec without the plan arrays.

        Keys must be cheap: a bucket probe that materializes the full
        padded edge arrays on device just to hash a tiling
        is most of a cold plan's cost.  Every spec field is derived here
        from the mem-CSR offsets alone.  The one data-dependent field,
        the chunk-span bound, only reads the padded rid stream at chunk
        boundaries: rid(k) for k < E is the CSR row containing slot k
        (``searchsorted(offsets[1:], k, 'right')``), and every padded
        slot holds the ``n_seg_pad`` sentinel.  The c0/c1 span count and
        pow2 rounding mirror ``sorted_ids_plan`` / ``_scatter_plan`` —
        ``_decompose_padded`` asserts the twin agrees with the real plan
        whenever one is built."""
        block_n, chunk_e = DEFAULT_BLOCK_N, DEFAULT_CHUNK_E
        e_real = int(problem.mem_sids.shape[0])
        e_pad = bucket_size(e_real, chunk_e)
        n_seg_pad = max(n_r_pad, block_n)
        n_chunks = e_pad // chunk_e
        off = np.asarray(problem.mem_offsets, dtype=np.int64)

        def ids_at(k: np.ndarray) -> np.ndarray:
            rid = np.searchsorted(off[1:], k, side="right")
            return np.where(k < e_real, rid, n_seg_pad)

        k_first = np.arange(n_chunks, dtype=np.int64) * chunk_e
        chunk_first = ids_at(k_first)
        chunk_last = ids_at(k_first + chunk_e - 1)
        bounds_lo = np.arange(n_seg_pad // block_n, dtype=np.int64) * block_n
        c0 = np.searchsorted(chunk_last, bounds_lo, side="left")
        c1 = np.searchsorted(chunk_first, bounds_lo + block_n, side="left")
        need = max(int(np.max(np.maximum(c1 - c0, 0), initial=0)), 1)
        max_chunks = round_up_pow2_chunks(need, n_chunks)
        return ScatterSpec(block_n=block_n, chunk_e=chunk_e,
                           max_chunks=max_chunks, n_seg_pad=n_seg_pad,
                           interpret=jax.default_backend() == "cpu")

    def bucket_key(self, problem: NucleusProblem,
                   config: Optional[NucleusConfig] = None) -> Tuple:
        """The hashable shape-class key (``stats['buckets']`` is indexed
        by it).  Derived from shapes + the mem-CSR offsets only — probing
        a key never builds padded plan arrays."""
        return tuple(self._bucket(problem, config or self.config).astuple())

    # -- manifest export / prewarm (the persistent warm path) --------------
    def manifest(self) -> Dict[str, Any]:
        """Serializable record of every decompose shape bucket this
        session has seen: the statics + padded shapes a compiled
        executable keys on, nothing graph-specific.

        ``serve.cache`` persists it next to jax's persistent compilation
        cache; a restarted server feeds it to ``prewarm`` so the first
        post-restart same-bucket decompose is a warm hit instead of a
        multi-second compile.  Stream-stage buckets (from ``update``) are
        excluded — they re-warm on first use and their keys are not
        shape-class records."""
        with self._stats_lock:
            items = list(self.stats["buckets"].items())
            meta = {k: dict(v) for k, v in self._bucket_meta.items()}
        entries = []
        for key, count in items:
            m = meta.get(key)
            if m is None or m.get("kind") != "decompose":
                continue
            b = _Bucket(*key)
            entries.append({
                "method": b.method, "r": b.r, "s": b.s, "fused": b.fused,
                "n_r_pad": b.n_r_pad, "n_s_pad": b.n_s_pad,
                "schedule": dataclasses.asdict(b.schedule),
                "pallas": None if b.pallas is None
                else dataclasses.asdict(b.pallas),
                "e_pad": m.get("e_pad"),
                "count": int(count)})
        return {"format": MANIFEST_FORMAT, "version": MANIFEST_VERSION,
                "config": self.config.to_dict(),
                "bucket_floor": self.bucket_floor,
                "bucket_cap": self.bucket_cap,
                "buckets": entries}

    def prewarm(self, manifest_or_buckets) -> int:
        """Compile each manifest bucket's executable before traffic.

        For every bucket record (a ``manifest()`` dict or its
        ``"buckets"`` list) an all-ghost padded problem with the bucket's
        exact shapes + statics is run through the dense engine: ghost
        s-rows (-1 ids) and pre-peeled r-cliques make the run trivially
        cheap, but the jitted computation — keyed on shapes and statics
        only — is byte-identical to a real member's, so the call either
        loads the executable from jax's persistent compilation cache
        (``serve.cache.init_persistent_cache``) or compiles and caches
        it.  The bucket is then registered warm: the first real
        same-bucket decompose counts as a warm hit and pays no compile.
        Returns the number of buckets prewarmed."""
        buckets = manifest_or_buckets
        if isinstance(buckets, dict):
            if buckets.get("format") != MANIFEST_FORMAT:
                raise ValueError(
                    f"not a session manifest: format="
                    f"{buckets.get('format')!r} (expected "
                    f"{MANIFEST_FORMAT!r}) — regenerate it with "
                    f"Session.manifest()")
            buckets = buckets["buckets"]
        done = 0
        for e in buckets:
            sched = PeelSchedule(**e["schedule"])
            spec = None if e.get("pallas") is None \
                else ScatterSpec(**e["pallas"])
            n_r_pad, n_s_pad = int(e["n_r_pad"]), int(e["n_s_pad"])
            C = comb(int(e["s"]), int(e["r"]))
            ghost = _PaddedProblem(
                inc_rid=jnp.full((n_s_pad, C), -1, INT),
                deg0=jnp.zeros((n_r_pad,), INT),
                n_r=n_r_pad, n_s=n_s_pad)
            plan = None
            meta: Dict[str, Any] = {"kind": "decompose"}
            if spec is not None:
                e_pad = int(e["e_pad"])
                meta["e_pad"] = e_pad
                # ghost plan arrays: every slot the padding sentinel —
                # the VALUES never enter the jit key, only the shapes do
                plan = (jnp.full((e_pad,), spec.n_seg_pad, INT),
                        jnp.zeros((e_pad,), INT), spec)
            out = dense_coreness(ghost, sched, use_pallas=spec is not None,
                                 max_rounds=n_r_pad + 2,
                                 hierarchy=bool(e["fused"]),
                                 peeled0=jnp.ones((n_r_pad,), bool),
                                 plan=plan)
            jax.block_until_ready(out)
            key = _Bucket(method=e["method"], r=int(e["r"]), s=int(e["s"]),
                          fused=bool(e["fused"]), n_r_pad=n_r_pad,
                          n_s_pad=n_s_pad, schedule=sched,
                          pallas=spec).astuple()
            with self._stats_lock:
                if key not in self.stats["buckets"]:
                    self.stats["buckets"][key] = 1
                    self._bucket_meta[key] = meta
                    self.stats["prewarmed"] += 1
            done += 1
        return done

    def _count(self, name: str, by: int = 1) -> None:
        """Lock-guarded counter bump (no lost updates under threads)."""
        with self._stats_lock:
            self.stats[name] += by

    def _bucket_hit(self, key: Tuple,
                    meta: Optional[Dict[str, Any]] = None) -> bool:
        """Count one engine call against ``key``'s bucket, LRU-style.

        ``stats['buckets']`` is insertion-ordered; a hit reinserts the
        key at the back, and opening a new bucket past ``bucket_cap``
        evicts the stalest entry (only the bookkeeping is bounded — the
        evicted executable may still sit in jax's compile cache, and a
        re-seen key simply counts cold again).  ``meta`` attaches the
        manifest extras of a decompose bucket (stream-stage keys carry
        none and stay out of the manifest).  Returns True when the
        bucket was already warm."""
        with self._stats_lock:
            buckets = self.stats["buckets"]
            seen = buckets.pop(key, 0)
            buckets[key] = seen + 1
            if meta is not None:
                self._bucket_meta[key] = meta
            if seen == 0 and self.bucket_cap \
                    and len(buckets) > self.bucket_cap:
                stale = next(iter(buckets))
                del buckets[stale]
                self._bucket_meta.pop(stale, None)
                self.stats["evictions"] += 1
            return seen > 0

    def _decompose_padded(self, problem: NucleusProblem,
                          config: NucleusConfig, plan, *,
                          wants_pallas: bool = False) -> Decomposition:
        fused = config.hierarchy == "fused"
        n_r, n_s, C = problem.n_r, problem.n_s, problem.n_sub
        bucket = self._bucket(problem, config, wants_pallas=wants_pallas)
        key = tuple(bucket.astuple())
        sched = bucket.schedule
        n_r_pad, n_s_pad = bucket.n_r_pad, bucket.n_s_pad
        meta: Dict[str, Any] = {"kind": "decompose"}
        if bucket.pallas is not None:
            # the plan-array length is part of the executable's jit key
            # but not of the hashable bucket key — record it so a
            # manifest prewarm can rebuild identically-shaped plan arrays
            meta["e_pad"] = bucket_size(int(problem.mem_sids.shape[0]),
                                        DEFAULT_CHUNK_E)
        warm = self._bucket_hit(key, meta=meta)
        self._count("warm" if warm else "cold")

        with span("session.pad", n_r=n_r, n_s=n_s, n_r_pad=n_r_pad,
                  n_s_pad=n_s_pad):
            inc = jnp.concatenate(
                [problem.inc_rid, jnp.full((n_s_pad - n_s, C), -1, INT)],
                axis=0)
            deg0 = jnp.concatenate(
                [problem.deg0, jnp.zeros((n_r_pad - n_r,), INT)])
            peeled0 = jnp.concatenate(
                [jnp.zeros((n_r,), bool), jnp.ones((n_r_pad - n_r,), bool)])
        padded = _PaddedProblem(inc_rid=inc, deg0=deg0, n_r=n_r_pad,
                                n_s=n_s_pad)
        kernel_plan = None
        if bucket.pallas is not None:
            # plan arrays materialize only here, on the execute path; the
            # bucket key came from the shape-derived spec twin, which must
            # agree with the real plan or warm members would miss the
            # executable the bucket promised
            with span("session.plan", e=int(problem.mem_sids.shape[0])):
                kernel_plan = self._pallas_plan(problem, n_r_pad)
            assert kernel_plan[2] == bucket.pallas, (
                "shape-derived ScatterSpec diverged from the real plan: "
                f"{bucket.pallas} vs {kernel_plan[2]}")
        sizes = {"n_r": n_r, "n_s": n_s, "n_r_pad": n_r_pad,
                 "n_s_pad": n_s_pad}
        with span("engine", fused=int(fused), **sizes) as engine:
            out = dense_coreness(padded, sched,
                                 use_pallas=kernel_plan is not None,
                                 max_rounds=n_r_pad + 2, hierarchy=fused,
                                 peeled0=peeled0, plan=kernel_plan)
            with span("engine.fetch"):
                core_raw = np.asarray(out[0])[:n_r]
                order_round = np.asarray(out[1])[:n_r]
                rounds, gens, live = (int(x) for x in out[2:5])
                uf_parent = uf_L = None
                if fused:
                    uf_parent = np.asarray(out[5])[:n_r]
                    uf_L = np.asarray(out[6])[:n_r]
            # the worklist's width: n_s·C links + n_r handoffs
            loop = {"fixpoint_gens": gens, "live_links": live,
                    "link_slots": gens * (n_s_pad * C + n_r_pad)}
            engine.add(rounds=rounds, **loop)
        if config.method == "approx":
            # same practical tightening as peel.approx_coreness: the
            # estimate never exceeds the original s-clique-degree, while
            # peel_value keeps the raw bucket values LINK equality saw
            core = np.minimum(core_raw, np.asarray(problem.deg0))
            peel_value = core_raw
        else:
            core, peel_value = core_raw, core_raw
        return Decomposition(config, problem=problem, core=core,
                             rounds=rounds, order_round=order_round,
                             peel_value=peel_value, uf_parent=uf_parent,
                             uf_L=uf_L, plan=plan,
                             counters={**loop, **sizes})

    # -- the padded sharded path -------------------------------------------
    def _decompose_padded_sharded(self, problem: NucleusProblem,
                                  config: NucleusConfig,
                                  plan) -> Decomposition:
        """Shape-bucketed ``shard_map`` peel: same artifact contract as the
        sharded backend's cold path, but the s-clique axis is padded to a
        SHARD-MULTIPLE shape class (``shard_bucket_size``) and ghost
        r-cliques enter pre-peeled, so near-miss shapes share one compiled
        ``shard_map`` executable (``distributed._jitted_decomposition``
        keys on the padded shapes + canonical schedule)."""
        from .distributed import sharded_decomposition_padded
        mesh = config.mesh
        if mesh is None:
            from ..launch.mesh import make_host_mesh
            mesh = make_host_mesh()
        n_dev = int(np.prod(mesh.devices.shape))
        fused = config.hierarchy == "fused"
        n_r, n_s, C = problem.n_r, problem.n_s, problem.n_sub
        bucket = _Bucket(
            method=config.method, r=config.r, s=config.s, fused=fused,
            n_r_pad=bucket_size(n_r, self.bucket_floor),
            n_s_pad=shard_bucket_size(n_s, n_dev, self.bucket_floor),
            schedule=canonical_schedule(config.method, C, config.delta,
                                        problem.g.n),
            shards=n_dev)
        n_r_pad, n_s_pad = bucket.n_r_pad, bucket.n_s_pad
        assert n_s_pad % n_dev == 0, (n_s_pad, n_dev)
        key = tuple(bucket.astuple())
        # kind "sharded" keeps these out of the manifest: prewarm rebuilds
        # dense executables only (a restarted server re-warms shard_map
        # buckets on first traffic)
        warm = self._bucket_hit(key, meta={"kind": "sharded"})
        self._count("warm" if warm else "cold")

        inc = jnp.concatenate(
            [problem.inc_rid, jnp.full((n_s_pad - n_s, C), -1, INT)], axis=0)
        deg0 = jnp.concatenate(
            [problem.deg0, jnp.zeros((n_r_pad - n_r,), INT)])
        peeled0 = jnp.concatenate(
            [jnp.zeros((n_r,), bool), jnp.ones((n_r_pad - n_r,), bool)])
        out = sharded_decomposition_padded(
            inc, deg0, peeled0, mesh, bucket.schedule,
            max_rounds=n_r_pad + 2, compress=config.compress,
            hierarchy=fused)
        core_raw = np.asarray(out[0])[:n_r]
        rounds = int(out[1])
        uf_parent = uf_L = None
        if fused:
            uf_parent = np.asarray(out[2])[:n_r]
            uf_L = np.asarray(out[3])[:n_r]
        if config.method == "approx":
            core = np.minimum(core_raw, np.asarray(problem.deg0))
            peel_value = core_raw
        else:
            core, peel_value = core_raw, core_raw
        # no order_round: the sharded engine records no trace
        # (records_trace=False), matching the cold sharded backend
        return Decomposition(config, problem=problem, core=core,
                             rounds=rounds, order_round=None,
                             peel_value=peel_value, uf_parent=uf_parent,
                             uf_L=uf_L, plan=plan)
