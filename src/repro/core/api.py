"""The front door: ``decompose(graph, config) -> Decomposition``.

The paper's value proposition is a *single* artifact — coreness plus the
join-forest hierarchy — built once and queried at many resolutions (Fig. 10).
This module is the one entry point that owns that artifact:

  * ``NucleusConfig`` captures every axis of the decomposition in one frozen,
    validated record: (r, s), exact vs approximate peeling, which backend
    executes the peel, which hierarchy strategy (if any) is attached, and the
    device knobs (Pallas scatter, mesh, collective compression).
    ``validate()`` rejects unsupported combinations with actionable errors
    instead of deep tracebacks (the legality matrix is DESIGN.md §6).
  * ``decompose`` builds the incidence structure if needed, runs the peel on
    the configured backend (the fused hierarchy rides inside the same jitted
    call), and returns a ``Decomposition``.
  * ``Decomposition`` owns the results *lazily with caching*: ``.core`` /
    ``.rounds`` are materialized by the peel; ``.tree`` materializes the
    ``HierarchyTree`` from the fused ``(uf_parent, uf_L)`` forest (or the
    configured builder) on first access; ``.cut(c)`` / ``.nuclei(c)`` answer
    Fig.-10 queries from the cached tree.  ``to_json()`` / ``from_json()``
    round-trip the whole artifact so a decomposition computed offline
    (sharded, multi-host) can be loaded and queried in a serving process
    (``python -m repro.launch.serve --arch nucleus``).

Everything below composes the existing building blocks (``peel``,
``interleaved``, ``hierarchy``, ``nuclei``, ``distributed``); the legacy
per-function surface survives as deprecated wrappers in ``repro.core``.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import backends as backend_registry
from .backends import (AUTO, BACKENDS, HIERARCHIES, METHODS, ConfigError,
                       Plan)
from .hierarchy import (HierarchyTree, build_hierarchy_basic,
                        build_hierarchy_levels)
from .incidence import BUILDS, NucleusProblem, build_problem
from .interleaved import (construct_tree_efficient, link_state_from_forest,
                          replay_trace)
from .nuclei import edge_density, nucleus_vertex_sets
from .peel import PeelResult
from .trace import span

JSON_FORMAT = "repro.nucleus-decomposition"
JSON_VERSION = 2
# version 1 artifacts (pre-Plan) load fine: "plan" is simply absent.
SUPPORTED_JSON_VERSIONS = (1, 2)


@dataclasses.dataclass(frozen=True)
class NucleusConfig:
    """Every axis of a nucleus decomposition, in one validated record.

    Axes (legality matrix in DESIGN.md §6):
      r, s        — the (r, s) of the decomposition, 1 <= r < s.
      method      — "exact" (ARB-NUCLEUS) or "approx" (Alg. 2, geometric
                    buckets); ``delta`` sets the approximation knob.
      backend     — a registered backend name ("dense": compiled
                    single-device engine, "gather": eager work-efficient
                    host loop, "sharded": shard_map over ``mesh``, "nh":
                    sequential baseline/oracle) or "auto" (the registry
                    planner picks from device kind, mesh availability,
                    problem size and memory budget; DESIGN.md §8).
      hierarchy   — "none", "fused" (LINK fixpoint inside the compiled
                    peel), "replay" (host trace replay), "two_phase"
                    (ANH-TE), "basic" (ANH-BL), or "auto" (richest
                    strategy the resolved backend supports).
      use_pallas  — force the Pallas scatter-decrement on/off (None =
                    backend default; dense backend only).
      mesh        — jax Mesh for the sharded backend (None = whatever this
                    host has, resolved at decompose() time).
      compress    — int16 + error-feedback delta all-reduce (sharded only).
      build       — incidence builder: "eager" (one-burst expansion),
                    "chunked" (memory-bounded source-vertex chunks +
                    two-pass count-then-fill assembly; DESIGN.md §7), or
                    "sharded" (chunks planned onto shards, per-shard slab
                    assembly + count-then-fill exchange;
                    ``repro.distbuild``, DESIGN.md §13).  All three are
                    bit-identical; chunked/sharded bound peak memory.
      memory_budget_bytes — chunked/sharded-build intermediate-memory
                    budget (None = a 256 MiB default); sets the chunk
                    size.  With backend='auto' the planner additionally
                    reads it as the machine's memory ceiling: if the dense
                    engine's per-round working set would exceed it, the
                    work-efficient gather backend is preferred — and if
                    the ESTIMATED EAGER BUILD working set exceeds it, the
                    build itself is upgraded to 'sharded' (multi-device)
                    or 'chunked' before the incidence structure is
                    materialized (the resolved plan's reasons name the
                    rule when it fires; DESIGN.md §8, §13).
      build_chunk_size — explicit source vertices per chunk (overrides the
                    budget-derived size; pins the sparse chunked path).
      build_shards — sharded-build worker count (None = this process's
                    ``jax.device_count()``, so build slabs line up with
                    the peel mesh; build='sharded' only).
    """

    r: int = 2
    s: int = 3
    method: str = "exact"
    delta: float = 0.1
    backend: str = "dense"
    hierarchy: str = "fused"
    use_pallas: Optional[bool] = None
    mesh: Optional[Any] = None
    compress: bool = False
    build: str = "eager"
    memory_budget_bytes: Optional[int] = None
    build_chunk_size: Optional[int] = None
    build_shards: Optional[int] = None

    def validate(self) -> "NucleusConfig":
        """Reject unsupported combinations with actionable errors.

        Backend x (method, hierarchy, knob) legality is DERIVED from the
        registry's capability declarations
        (``backends.check_capabilities``) — this method holds only the
        backend-independent axis checks.  ``backend='auto'`` /
        ``hierarchy='auto'`` are accepted here; the planner resolves them
        at decompose() time and the resolved config re-validates.
        """
        if not (1 <= self.r < self.s):
            raise ConfigError(
                f"need 1 <= r < s, got (r, s) = ({self.r}, {self.s})")
        if self.method not in METHODS:
            raise ConfigError(
                f"method={self.method!r}; expected one of {METHODS}")
        # membership is checked against the LIVE registry, so a backend
        # registered at runtime is immediately legal (BACKENDS is the
        # import-time snapshot kept for display/tests)
        if self.backend != AUTO and \
                self.backend not in backend_registry.names():
            raise ConfigError(
                f"backend={self.backend!r}; expected one of "
                f"{backend_registry.names() + (AUTO,)}")
        if self.hierarchy != AUTO and self.hierarchy not in HIERARCHIES:
            raise ConfigError(
                f"hierarchy={self.hierarchy!r}; expected one of "
                f"{HIERARCHIES + (AUTO,)}")
        if self.method == "approx" and not self.delta > 0:
            raise ConfigError(
                f"method='approx' needs delta > 0, got {self.delta}")
        backend_registry.check_capabilities(self)
        if self.build not in BUILDS:
            raise ConfigError(
                f"build={self.build!r}; expected one of {BUILDS}")
        if self.memory_budget_bytes is not None:
            # the budget sizes the chunked/sharded builders; with
            # backend='auto' it is ALSO the planner's memory ceiling (and
            # can upgrade the build itself), so it stays legal there even
            # with build='eager'
            if self.build not in ("chunked", "sharded") and \
                    self.backend != AUTO:
                raise ConfigError(
                    "memory_budget_bytes sizes the chunked/sharded "
                    "incidence builders (or guides backend='auto'); set "
                    "build='chunked'/'sharded', backend='auto', or drop "
                    "the budget")
            if self.memory_budget_bytes <= 0:
                raise ConfigError(
                    f"memory_budget_bytes must be positive, got "
                    f"{self.memory_budget_bytes}")
        if self.build_chunk_size is not None:
            if self.build not in ("chunked", "sharded"):
                raise ConfigError(
                    "build_chunk_size is the chunked/sharded builders' "
                    "chunk; set build='chunked'/'sharded' or drop it")
            if self.build_chunk_size <= 0:
                raise ConfigError(
                    f"build_chunk_size must be positive, got "
                    f"{self.build_chunk_size}")
        if self.build_shards is not None:
            if self.build != "sharded":
                raise ConfigError(
                    "build_shards is the sharded builder's worker count; "
                    "set build='sharded' or drop it")
            if self.build_shards <= 0:
                raise ConfigError(
                    f"build_shards must be positive, got "
                    f"{self.build_shards}")
        return self

    @classmethod
    def legal_combinations(cls) -> List[Tuple[str, str, str]]:
        """Every (method, backend, hierarchy) triple ``validate()`` accepts.

        The single source of the legality matrix — the facade parity suite
        iterates it and DESIGN.md §6 documents it.
        """
        out = []
        for method in METHODS:
            for backend in backend_registry.names():  # live registry
                for hierarchy in HIERARCHIES:
                    cfg = cls(method=method, backend=backend,
                              hierarchy=hierarchy)
                    try:
                        cfg.validate()
                    except ConfigError:
                        continue
                    out.append((method, backend, hierarchy))
        return out

    def to_dict(self) -> Dict[str, Any]:
        """JSON-safe view (the mesh is a process-local handle, not state)."""
        d = dataclasses.asdict(self)
        d.pop("mesh")
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "NucleusConfig":
        return cls(**{k: v for k, v in d.items() if k != "mesh"})


@dataclasses.dataclass(frozen=True)
class Nucleus:
    """One c-(r, s) nucleus: its vertex set + the Fig. 10 quality metric."""

    label: int
    vertices: np.ndarray   # sorted unique vertex ids
    n_r_cliques: int       # r-cliques carrying the nucleus
    density: float         # |E(S)| / C(|S|, 2); nan if edges unavailable


def _ints(x) -> List[int]:
    return [int(v) for v in np.asarray(x).reshape(-1)]


def _opt_ints(x) -> Optional[List[int]]:
    return None if x is None else _ints(x)


class Decomposition:
    """The build-once/query-many artifact: coreness + hierarchy + queries.

    Materialization contract (DESIGN.md §6): the peel (``core``, ``rounds``,
    the trace, and — for hierarchy='fused' — the join forest) is computed by
    ``decompose()``; everything downstream is lazy and cached:

      .tree      — first access builds the ``HierarchyTree`` from the fused
                   forest / trace replay / configured two-phase builder.
      .cut(c)    — first call per level walks the tree; repeats are O(1).
      .nuclei(c) — vertex sets + densities, derived from the cached cut.

    ``to_json()`` pins the artifact (tree materialized, inputs the queries
    need embedded), so ``from_json()`` serves queries with no
    ``NucleusProblem`` and no recomputation.

    ``counters`` holds the peel's loop counts where the Session's padded
    dense path ran it (``fixpoint_gens``, ``live_links``, ``link_slots``
    and the unpadded and padded ``n_r``/``n_s``; PERF.md §3); None on
    paths that do not count (sharded, fallback, streaming, JSON).
    """

    def __init__(self, config: NucleusConfig, *,
                 problem: Optional[NucleusProblem] = None,
                 core: np.ndarray, rounds: int,
                 order_round: Optional[np.ndarray] = None,
                 peel_value: Optional[np.ndarray] = None,
                 uf_parent: Optional[np.ndarray] = None,
                 uf_L: Optional[np.ndarray] = None,
                 tree: Optional[HierarchyTree] = None,
                 r_cliques: Optional[np.ndarray] = None,
                 edges: Optional[np.ndarray] = None,
                 n_vertices: Optional[int] = None,
                 n_s: Optional[int] = None,
                 plan: Optional[Plan] = None,
                 name: Optional[str] = None,
                 version: int = 0,
                 counters: Optional[Dict[str, int]] = None):
        self.config = config
        self.counters = counters
        # set by the serve.Router that produced it; its query spans repeat it
        self.request_id: Optional[int] = None
        self._name = name
        self._version = int(version)
        self._plan = plan
        self.problem = problem
        self._core = np.asarray(core)
        self._rounds = int(rounds)
        self._order_round = None if order_round is None \
            else np.asarray(order_round)
        self._peel_value = self._core if peel_value is None \
            else np.asarray(peel_value)
        self._uf_parent = None if uf_parent is None else np.asarray(uf_parent)
        self._uf_L = None if uf_L is None else np.asarray(uf_L)
        self._tree = tree
        self._r_cliques = None if r_cliques is None else np.asarray(r_cliques)
        self._edges = None if edges is None else np.asarray(edges)
        self._n_vertices = n_vertices
        self._n_s = n_s
        self._cuts: Dict[int, np.ndarray] = {}
        self._nuclei: Dict[int, Dict[int, "Nucleus"]] = {}
        self._link_stats: Optional[Tuple[int, int]] = None

    # -- materialized by decompose() --------------------------------------
    @property
    def core(self) -> np.ndarray:
        """(n_r,) core numbers (approx: clipped practical estimates)."""
        return self._core

    @property
    def rounds(self) -> int:
        """Peel rounds (the span / all-reduce count proxy)."""
        return self._rounds

    @property
    def order_round(self) -> Optional[np.ndarray]:
        """(n_r,) round each r-clique peeled — the on-device trace (None on
        backends that do not record it: sharded, nh)."""
        return self._order_round

    @property
    def peel_value(self) -> np.ndarray:
        """(n_r,) raw bucket values (unclipped) — what LINK equality saw."""
        return self._peel_value

    @property
    def n_r(self) -> int:
        return int(self._core.shape[0])

    # -- live-artifact identity --------------------------------------------
    @property
    def name(self) -> Optional[str]:
        """The serving-side artifact name (None until published).  A
        router publishing this decomposition under a tenant-visible name
        sets it; ``update()`` carries it to the successor artifact."""
        return self._name

    @name.setter
    def name(self, value: Optional[str]) -> None:
        self._name = value

    @property
    def version(self) -> int:
        """Monotone live-artifact version: 0 at decompose() time, +1 per
        ``update(delta)`` — what a status endpoint reports so clients can
        tell which edit generation answered their query."""
        return self._version

    @property
    def has_hierarchy(self) -> bool:
        return self.config.hierarchy != "none"

    @property
    def link_stats(self) -> Optional[Tuple[int, int]]:
        """(links processed, unions) of the host LINK replay — populated
        only after hierarchy='replay' materializes the tree (the fused
        fixpoint runs on device and does not count)."""
        return self._link_stats

    @property
    def uf_parent(self) -> Optional[np.ndarray]:
        """(n_r,) resolved ANH-EL union-find — the join forest (fused:
        computed by decompose(); replay: after .tree materializes)."""
        return self._uf_parent

    @property
    def uf_L(self) -> Optional[np.ndarray]:
        """(n_r,) nearest-lower-core table of the join forest."""
        return self._uf_L

    # -- the planner's decision record -------------------------------------
    @property
    def plan(self) -> Optional[Plan]:
        """How backend/hierarchy were resolved (requested vs resolved +
        reasons).  None only on artifacts serialized before plans existed
        (JSON version 1)."""
        return self._plan

    def plan_report(self) -> str:
        """Human-readable resolution report (what quickstart prints)."""
        if self._plan is None:
            return "plan: not recorded (artifact predates plan embedding)"
        return self._plan.report()

    # -- lazy hierarchy ----------------------------------------------------
    @property
    def tree(self) -> HierarchyTree:
        """The hierarchy tree, materialized on first access and cached."""
        if self._tree is not None:
            return self._tree
        with span("tree", rid=self.request_id, n_r=self.n_r):
            return self._build_tree()

    def _build_tree(self) -> HierarchyTree:
        h = self.config.hierarchy
        if h == "none":
            raise ValueError(
                "this Decomposition was built with hierarchy='none'; "
                "re-run decompose() with hierarchy='fused' (or 'replay'/"
                "'two_phase'/'basic') to get a tree")
        if h in ("fused", "replay") and self._uf_parent is None:
            # replay defers the host LINK fixpoint until the tree is needed
            if self.problem is None or self._order_round is None:
                raise ValueError(
                    "cannot materialize the hierarchy: the join forest was "
                    "not computed and the peel trace / problem is not "
                    "available (serialize with to_json() *after* the tree "
                    "exists, or keep the NucleusProblem attached)")
            res = PeelResult(core=self._core, rounds=self._rounds,
                             order_round=self._order_round,
                             peel_value=self._peel_value)
            state = replay_trace(self.problem, res)
            from .interleaved import _resolve
            self._link_stats = (state.stats_links, state.stats_unions)
            self._uf_parent = _resolve(state.parent,
                                       np.arange(self.n_r, dtype=np.int64))
            self._uf_L = state.L.copy()
        if h in ("fused", "replay"):
            state = link_state_from_forest(self._peel_value, self._uf_parent,
                                           self._uf_L)
            self._tree = construct_tree_efficient(self._problem_view(), state)
        elif h == "two_phase":
            self._tree = build_hierarchy_levels(self._require_problem(),
                                                self._core)
        elif h == "basic":
            self._tree = build_hierarchy_basic(self._require_problem(),
                                               self._core)
        return self._tree

    def _require_problem(self) -> NucleusProblem:
        if self.problem is None:
            raise ValueError(
                f"hierarchy={self.config.hierarchy!r} rebuilds the tree "
                "from the incidence structure, which a deserialized "
                "Decomposition does not carry; serialize with to_json() "
                "after the tree is materialized (to_json() does this) or "
                "keep the NucleusProblem attached")
        return self.problem

    class _TreeProblemView:
        """The construct-tree post-pass only reads ``n_r``."""

        def __init__(self, n_r: int):
            self.n_r = n_r

    def _problem_view(self):
        return self.problem if self.problem is not None \
            else self._TreeProblemView(self.n_r)

    # -- queries -----------------------------------------------------------
    def cut(self, c: int) -> np.ndarray:
        """Label each r-clique with its c-(r, s) nucleus id (-1: core < c).

        First call per level walks the cached tree; repeats return the
        cached labels (the serving hot path).
        """
        c = int(c)
        if c not in self._cuts:
            with span("cut", rid=self.request_id, c=c):
                self._cuts[c] = self.tree.ancestor_at_level(c)
        return self._cuts[c]

    def nuclei(self, c: int) -> Dict[int, Nucleus]:
        """The c-(r, s) nuclei as vertex sets + densities (Fig. 10).

        Cached per level, like ``cut`` — repeats are dict hits (the
        serving hot path)."""
        c = int(c)
        if c in self._nuclei:
            return self._nuclei[c]
        with span("nuclei", rid=self.request_id, c=c):
            self._nuclei[c] = self._vertex_sets(c)
        return self._nuclei[c]

    def _vertex_sets(self, c: int) -> Dict[int, Nucleus]:
        labels = self.cut(c)
        rc = self._r_cliques if self._r_cliques is not None else (
            None if self.problem is None
            else np.asarray(self.problem.r_cliques))
        if rc is None:
            raise ValueError(
                "nucleus vertex sets need the r-clique table; serialize "
                "with to_json(include_inputs=True) or keep the "
                "NucleusProblem attached")
        edges = self._edges if self._edges is not None else (
            None if self.problem is None
            else np.asarray(self.problem.g.edges))
        out = {}
        sets = nucleus_vertex_sets(rc, labels)
        pos = np.asarray(labels)
        labs, cnts = np.unique(pos[pos >= 0], return_counts=True)
        counts = dict(zip(labs.tolist(), cnts.tolist()))
        for lab, verts in sets.items():
            dens = edge_density(edges, verts) if edges is not None \
                else float("nan")
            out[int(lab)] = Nucleus(label=int(lab), vertices=verts,
                                    n_r_cliques=int(counts[lab]),
                                    density=dens)
        return out

    # -- incremental maintenance -------------------------------------------
    def update(self, delta, *, bucket_hook=None) -> "Decomposition":
        """Apply a ``GraphDelta`` (edge inserts/deletes) incrementally.

        Returns a NEW ``Decomposition`` for the edited graph — core
        values, peel values, the fused join forest, and every downstream
        query (``tree``/``cut``/``nuclei``) are array-for-array identical
        to a fresh ``decompose()`` of the edited graph (the parity tests
        pin this), but only the affected neighborhood is recomputed
        (``repro.core.streaming``; DESIGN.md §10).  ``self`` is left
        untouched and remains valid for the OLD graph.

        Caveats: exact method only, (r, s) in ``streaming.SUPPORTED_RS``,
        hierarchy 'fused' or 'none', and the ``NucleusProblem`` must
        still be attached.  The returned artifact has no peel trace
        (``order_round=None``, ``rounds == -1``) and carries an
        ``update_stats`` telemetry record.  ``bucket_hook`` (internal)
        lets ``Session.update`` count the padded-shape buckets the
        compiled local stages hit.
        """
        from .streaming import update_decomposition
        new_dec, _stats = update_decomposition(self, delta,
                                               bucket_hook=bucket_hook)
        return new_dec

    # -- serialization -----------------------------------------------------
    def to_json(self, include_inputs: bool = True) -> str:
        """Serialize the full artifact (deterministic, round-trip exact).

        The tree is materialized first so a loaded Decomposition answers
        ``cut``/``nuclei`` without the incidence structure;
        ``include_inputs`` embeds the r-clique table + graph edges the
        nucleus/density queries need (skip it to ship core + tree only).
        """
        tree = self.tree if self.has_hierarchy else None
        d: Dict[str, Any] = {
            "format": JSON_FORMAT,
            "version": JSON_VERSION,
            "config": self.config.to_dict(),
            "n_r": self.n_r,
            "n_s": self._n_s if self._n_s is not None else (
                None if self.problem is None else self.problem.n_s),
            "n_vertices": self._n_vertices if self._n_vertices is not None
            else (None if self.problem is None else int(self.problem.g.n)),
            "rounds": self._rounds,
            "name": self._name,
            "live_version": self._version,
            "core": _ints(self._core),
            "order_round": _opt_ints(self._order_round),
            "peel_value": _ints(self._peel_value),
            "uf_parent": _opt_ints(self._uf_parent),
            "uf_L": _opt_ints(self._uf_L),
            "plan": None if self._plan is None else self._plan.to_dict(),
            "tree": None if tree is None else {
                "n_leaves": tree.n_leaves,
                "parent": _ints(tree.parent),
                "level": _ints(tree.level),
            },
        }
        if include_inputs:
            rc = self._r_cliques if self._r_cliques is not None else (
                None if self.problem is None
                else np.asarray(self.problem.r_cliques))
            ed = self._edges if self._edges is not None else (
                None if self.problem is None
                else np.asarray(self.problem.g.edges))
            d["r_cliques"] = None if rc is None else \
                [_ints(row) for row in np.asarray(rc)]
            d["edges"] = None if ed is None else \
                [_ints(row) for row in np.asarray(ed)]
        else:
            d["r_cliques"] = None
            d["edges"] = None
        return json.dumps(d, sort_keys=True, separators=(",", ":"))

    @classmethod
    def from_json(cls, blob: str) -> "Decomposition":
        """Load a serialized decomposition for query serving.

        The result has no ``NucleusProblem``; ``cut``/``nuclei`` answer from
        the embedded tree + inputs, and ``to_json()`` round-trips exactly.
        """
        d = json.loads(blob)
        if d.get("format") != JSON_FORMAT:
            raise ValueError(
                f"not a serialized Decomposition: format={d.get('format')!r}"
                f" (expected {JSON_FORMAT!r}) — this file was not written "
                f"by Decomposition.to_json(); regenerate the artifact with "
                f"decompose(...).save(path)")
        if d.get("version") not in SUPPORTED_JSON_VERSIONS:
            raise ValueError(
                f"unsupported Decomposition version {d.get('version')!r}: "
                f"this build reads versions {SUPPORTED_JSON_VERSIONS} and "
                f"writes {JSON_VERSION} — the artifact was written by a "
                f"different repro version; regenerate it with to_json()/"
                f"save() or upgrade the serving process")
        config = NucleusConfig.from_dict(d["config"])
        plan_d = d.get("plan")
        arr = lambda x: None if x is None else np.asarray(x, np.int64)
        t = d.get("tree")
        tree = None if t is None else HierarchyTree(
            n_leaves=int(t["n_leaves"]),
            parent=np.asarray(t["parent"], np.int64),
            level=np.asarray(t["level"], np.int64))
        rc = d.get("r_cliques")
        ed = d.get("edges")
        return cls(config,
                   core=np.asarray(d["core"], np.int64),
                   rounds=int(d["rounds"]),
                   order_round=arr(d.get("order_round")),
                   peel_value=np.asarray(d["peel_value"], np.int64),
                   uf_parent=arr(d.get("uf_parent")),
                   uf_L=arr(d.get("uf_L")),
                   tree=tree,
                   r_cliques=None if rc is None
                   else np.asarray(rc, np.int64).reshape(-1, config.r),
                   edges=None if ed is None
                   else np.asarray(ed, np.int64).reshape(-1, 2),
                   n_vertices=d.get("n_vertices"),
                   n_s=d.get("n_s"),
                   plan=None if plan_d is None else Plan.from_dict(plan_d),
                   name=d.get("name"),
                   version=int(d.get("live_version", 0)))

    def save(self, path: str, include_inputs: bool = True) -> None:
        with open(path, "w") as f:
            f.write(self.to_json(include_inputs=include_inputs))
            f.write("\n")

    @classmethod
    def load(cls, path: str) -> "Decomposition":
        with open(path) as f:
            return cls.from_json(f.read())

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return (f"Decomposition(r={self.config.r}, s={self.config.s}, "
                f"method={self.config.method!r}, "
                f"backend={self.config.backend!r}, "
                f"hierarchy={self.config.hierarchy!r}, n_r={self.n_r}, "
                f"rounds={self._rounds}, "
                f"tree={'materialized' if self._tree is not None else 'lazy'})")


def resolve_problem(graph_or_problem,
                    config: NucleusConfig
                    ) -> Tuple[NucleusProblem, NucleusConfig]:
    """The front doors' shared input prologue: validate the config, build
    the incidence structure from a ``Graph`` (threading every build knob),
    or adopt a prebuilt ``NucleusProblem`` (its (r, s) wins).  Shared by
    ``decompose()`` and ``Session`` so the build stage cannot drift.

    Build auto-upgrade (DESIGN.md §13): with ``backend='auto'``, a
    ``memory_budget_bytes``, and the default eager build, the estimated
    eager expansion working set is compared against the budget BEFORE the
    build runs; if it does not fit, the build is upgraded to 'sharded'
    (multi-device — slabs line up with the peel mesh) or 'chunked'
    (single device).  Output is bit-identical either way, so the upgrade
    changes peak memory, never results."""
    if isinstance(graph_or_problem, NucleusProblem):
        problem = graph_or_problem
        if (problem.r, problem.s) != (config.r, config.s):
            config = dataclasses.replace(config, r=problem.r, s=problem.s)
        config.validate()
    else:
        config.validate()
        if config.backend == AUTO and config.build == "eager" and \
                config.memory_budget_bytes is not None:
            import jax
            from ..distbuild import estimate_eager_build_bytes
            from .incidence import pick_rank
            dg, _ = pick_rank(graph_or_problem)
            if estimate_eager_build_bytes(dg, config.s) > \
                    config.memory_budget_bytes:
                upgraded = "sharded" if len(jax.devices()) > 1 else "chunked"
                config = dataclasses.replace(config, build=upgraded)
        problem = build_problem(
            graph_or_problem, config.r, config.s, build=config.build,
            memory_budget_bytes=config.memory_budget_bytes,
            chunk_size=config.build_chunk_size,
            shards=config.build_shards)
    return problem, config


def plan_config(problem: NucleusProblem,
                config: NucleusConfig) -> Tuple[NucleusConfig, Plan]:
    """Resolve ``backend='auto'``/``hierarchy='auto'`` against ``problem``,
    and on the dense backend ``use_pallas=None`` to the plan's kernel.

    Returns the concrete, re-validated config plus the ``Plan`` decision
    record (explicit configs get a trivial plan).  Shared by
    ``decompose()`` and ``Session`` so the two front doors cannot drift.
    """
    stats = problem.build_stats or {}
    plan = backend_registry.resolve_plan(
        config, n_r=problem.n_r, n_s=problem.n_s, n_sub=problem.n_sub,
        r=problem.r, s=problem.s, build=stats.get("build", config.build),
        eager_build_bytes=stats.get("eager_estimate_bytes"))
    if stats.get("build") == "sharded":
        # build telemetry rides the plan reasons so plan_report() (and the
        # serve report) shows HOW the incidence structure was distributed
        plan = dataclasses.replace(plan, reasons=plan.reasons + (
            f"build 'sharded': {stats.get('n_shards')} shards x "
            f"{stats.get('n_chunks')} chunks "
            f"(chunks/shard={stats.get('chunks_per_shard')}), "
            f"work skew {stats.get('skew'):.3f}, "
            f"exchange {stats.get('exchange_bytes')} B",))
    if (plan.backend, plan.hierarchy) != (config.backend, config.hierarchy):
        config = dataclasses.replace(config, backend=plan.backend,
                                     hierarchy=plan.hierarchy)
    if plan.use_pallas is not None and config.use_pallas != plan.use_pallas:
        # the engine runs the kernel the plan names, not a second guess
        config = dataclasses.replace(config, use_pallas=plan.use_pallas)
    config.validate()
    return config, plan


def decompose(graph_or_problem, config: Optional[NucleusConfig] = None,
              **overrides) -> Decomposition:
    """THE entry point: run an (r, s) nucleus decomposition per ``config``.

    ``graph_or_problem`` is a ``Graph`` (the incidence structure is built
    here from ``config.r/s``) or a prebuilt ``NucleusProblem`` (its (r, s)
    wins).  ``config`` defaults to ``NucleusConfig()``; keyword overrides
    are applied on top, e.g. ``decompose(g, method="approx", delta=0.5)``.
    ``backend='auto'``/``hierarchy='auto'`` are resolved here by the
    registry planner (``backends.resolve_plan``); the decision is recorded
    on the result (``.plan`` / ``plan_report()``) and serialized with it.

    The peel runs now (fused hierarchy included — one jitted call on the
    dense backend) on the registered backend the config names; tree
    materialization and cut/nuclei queries are lazy on the returned
    ``Decomposition``.
    """
    if config is None:
        config = NucleusConfig()
    if overrides:
        config = dataclasses.replace(config, **overrides)
    problem, config = resolve_problem(graph_or_problem, config)
    config, plan = plan_config(problem, config)
    return execute_plan(problem, config, plan)


def execute_plan(problem: NucleusProblem, config: NucleusConfig,
                 plan: Plan) -> Decomposition:
    """Run an already-planned decomposition: registry lookup + dispatch.

    ``config`` must be concrete (post-``plan_config``); ``plan`` is the
    decision record to attach.  ``Session`` calls this directly on its
    fallback path so the planner's provenance (requested='auto' + reasons)
    survives instead of being re-derived from the resolved config.
    """
    res = backend_registry.get(config.backend).run(problem, config)
    return Decomposition(config, problem=problem, core=res.core,
                         rounds=res.rounds, order_round=res.order_round,
                         peel_value=res.peel_value, uf_parent=res.uf_parent,
                         uf_L=res.uf_L, plan=plan)
