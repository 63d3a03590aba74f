"""Capability-declared backend registry + the auto-planner.

Every peel backend is a registered ``Backend``: a name, a declarative
``BackendCapabilities`` record, and ``run(problem, config) ->
BackendResult``.  The registry is the single source of backend truth:

  * ``NucleusConfig.validate()`` derives the legality matrix from the
    capability declarations (``check_capabilities``) — there are no
    hand-coded per-backend branches anywhere; adding a backend is one
    ``register()`` call and the matrix, the error messages and
    ``legal_combinations()`` all follow.
  * ``decompose()`` dispatches by registry lookup (``get``), not if/elif.
  * ``resolve_plan`` is the ``backend="auto"`` / ``hierarchy="auto"``
    planner: it filters the registry down to capability-compatible
    candidates, then picks by device kind, mesh availability, problem
    size and ``memory_budget_bytes`` (decision rules in DESIGN.md §8).
    The resolved ``Plan`` (requested vs resolved + human-readable
    reasons) is recorded on every ``Decomposition`` and embedded in
    ``to_json()``.

Capability semantics (how legality is *derived*, DESIGN.md §8):

  * ``hierarchy='fused'`` is legal iff the backend has a compiled peel
    loop to fuse the LINK fixpoint into (``compiled_peel``).
  * ``hierarchy='replay'`` is legal iff the backend records the peel
    trace the host replay consumes (``records_trace``).
  * ``'none'``/``'two_phase'``/``'basic'`` need only core numbers, so
    every backend supports them.
  * the device knobs (``use_pallas``/``mesh``/``compress``) are legal
    iff the backend lists them in ``knobs``.

This module must stay import-light (``api`` imports it at module load):
backend implementations are imported lazily inside the ``run`` adapters.
"""
from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Dict, List, Optional, Protocol, Tuple,
                    runtime_checkable)

import numpy as np

from . import planner_profile
from .incidence import NucleusProblem

METHODS = ("exact", "approx")
HIERARCHIES = ("none", "fused", "replay", "two_phase", "basic")
KNOBS = ("pallas", "mesh", "compress")
AUTO = "auto"


class ConfigError(ValueError):
    """An unsupported ``NucleusConfig`` combination (caught at validate())."""


@dataclasses.dataclass(frozen=True)
class BackendCapabilities:
    """What a backend declares it can do — legality is derived from this.

    ``methods``: peel schedules the backend runs ("exact"/"approx").
    ``compiled_peel``: the peel is one compiled loop, so the LINK fixpoint
        can fuse into it (``hierarchy='fused'`` legal).
    ``records_trace``: the backend returns the on-device peel trace
        (``order_round``), so host replay can rebuild the forest
        (``hierarchy='replay'`` legal).
    ``knobs``: device knobs the backend honours ("pallas"/"mesh"/
        "compress").
    ``fast_lanes``: special-case engine lanes the backend routes to by
        itself (e.g. "kcore": the r1s2 vertex-degree peel with the
        one-shot edge-list link fixpoint) — declared so the planner can
        *record* the routing in ``Plan.reasons``; legality is unaffected
        (a fast lane is an internal specialization, not a capability a
        config can request).
    ``summary``: one-line description, quoted in derived error messages
        and ``plan_report()``.
    """

    methods: Tuple[str, ...]
    compiled_peel: bool
    records_trace: bool
    knobs: frozenset
    summary: str
    fast_lanes: Tuple[str, ...] = ()

    @property
    def hierarchies(self) -> Tuple[str, ...]:
        """Supported hierarchy strategies, derived — not hand-listed."""
        return tuple(h for h in HIERARCHIES
                     if (h != "fused" or self.compiled_peel)
                     and (h != "replay" or self.records_trace))


@dataclasses.dataclass(frozen=True)
class BackendResult:
    """What ``Backend.run`` returns: host-side arrays + normalized scalars.

    ``rounds`` is always a python int (every adapter coerces — the old
    facade's sharded+fused branch forgot to); optional fields are None
    exactly when the capabilities say the backend does not produce them
    (``order_round``/``peel_value`` need ``records_trace``;
    ``uf_parent``/``uf_L`` need a fused hierarchy).
    """

    core: np.ndarray
    rounds: int
    order_round: Optional[np.ndarray] = None
    peel_value: Optional[np.ndarray] = None
    uf_parent: Optional[np.ndarray] = None
    uf_L: Optional[np.ndarray] = None


@runtime_checkable
class Backend(Protocol):
    """The registry entry contract (structural — see ``_Registered``)."""

    name: str
    capabilities: BackendCapabilities

    def run(self, problem: NucleusProblem, config) -> BackendResult:
        ...


@dataclasses.dataclass(frozen=True)
class _Registered:
    name: str
    capabilities: BackendCapabilities
    _run: Callable[[NucleusProblem, Any], BackendResult]

    def run(self, problem: NucleusProblem, config) -> BackendResult:
        return self._run(problem, config)


_REGISTRY: Dict[str, Backend] = {}


def register(backend: Backend) -> Backend:
    """Register a backend (insertion order defines enumeration order)."""
    if backend.name in _REGISTRY:
        raise ValueError(f"backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend
    return backend


def names() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def get(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ConfigError(
            f"backend={name!r}; expected one of {names()} (or 'auto')")


def all_backends() -> Tuple[Backend, ...]:
    return tuple(_REGISTRY.values())


# ---------------------------------------------------------------------------
# Capability-derived validation: the ONLY place config x backend legality
# lives.  Messages are rule templates formatted with registry-derived
# alternatives — never hand-coded per backend.
# ---------------------------------------------------------------------------

_HIERARCHY_RULES = {
    "fused": (
        "compiled_peel",
        "hierarchy='fused' runs the LINK fixpoint inside the compiled peel "
        "loop, but backend={backend!r} has no compiled loop to fuse into; "
        "use hierarchy='replay' (same forest, host fixpoint) or one of "
        "backend={alts}"),
    "replay": (
        "records_trace",
        "hierarchy='replay' rebuilds the forest from the recorded peel "
        "trace, which backend={backend!r} does not return; use "
        "hierarchy='fused' (forest computed in the same loop) or "
        "'two_phase', or one of backend={alts}"),
}

_KNOB_RULES = {
    "pallas": (
        lambda cfg: bool(cfg.use_pallas),
        "use_pallas=True selects the Pallas scatter-decrement of the "
        "compiled dense engine; backend={backend!r} never runs it — use "
        "one of backend={alts} or drop use_pallas"),
    "compress": (
        lambda cfg: bool(cfg.compress),
        "compress=True (int16 + error-feedback delta all-reduce) only "
        "applies to a sharded collective, which backend={backend!r} does "
        "not run; use one of backend={alts} or drop compress"),
    "mesh": (
        lambda cfg: cfg.mesh is not None,
        "a mesh only applies to one of backend={alts}, got "
        "backend={backend!r}"),
}


def _hierarchy_supported(caps: BackendCapabilities, hierarchy: str) -> bool:
    rule = _HIERARCHY_RULES.get(hierarchy)
    return rule is None or getattr(caps, rule[0])


def _method_alts(method: str) -> Tuple[str, ...]:
    return tuple(b.name for b in all_backends()
                 if method in b.capabilities.methods)


def check_capabilities(config) -> None:
    """Raise ConfigError iff ``config`` asks a backend for something its
    capability declaration rules out.  ``backend='auto'`` defers the
    per-backend checks to the planner but still requires at least one
    capability-compatible candidate to exist."""
    if config.backend == AUTO:
        if not candidate_backends(config):
            raise ConfigError(
                f"backend='auto': no registered backend supports "
                f"method={config.method!r} with "
                f"hierarchy={config.hierarchy!r} and the requested knobs "
                f"(use_pallas={config.use_pallas}, "
                f"mesh={'set' if config.mesh is not None else None}, "
                f"compress={config.compress}); registered: {names()}")
        return
    caps = get(config.backend).capabilities
    if config.method not in caps.methods:
        raise ConfigError(
            f"backend={config.backend!r} is {caps.summary} — "
            f"method={config.method!r} needs one of "
            f"backend={_method_alts(config.method)}")
    if config.hierarchy != AUTO and \
            not _hierarchy_supported(caps, config.hierarchy):
        attr, template = _HIERARCHY_RULES[config.hierarchy]
        alts = tuple(b.name for b in all_backends()
                     if getattr(b.capabilities, attr))
        raise ConfigError(template.format(backend=config.backend, alts=alts))
    for knob, (is_set, template) in _KNOB_RULES.items():
        if is_set(config) and knob not in caps.knobs:
            alts = tuple(b.name for b in all_backends()
                         if knob in b.capabilities.knobs)
            raise ConfigError(
                template.format(backend=config.backend, alts=alts))


# ---------------------------------------------------------------------------
# The auto-planner: backend="auto" / hierarchy="auto" resolution
# ---------------------------------------------------------------------------

# Decision thresholds (DESIGN.md §8).  TINY_NR: below this, an eager host
# loop beats paying an XLA compile for a one-shot decomposition on CPU.
# SHARD_MIN_INCIDENCE: minimum n_s * C incidence entries before slicing the
# s-clique axis across devices beats single-device overheadlessness.
# DENSE_ROUND_BYTES_PER_ENTRY: the dense engine touches the whole (n_s, C)
# incidence plus two boolean/int views of it every round (~3 int32 reads);
# if that working set exceeds memory_budget_bytes, the work-efficient
# gather backend (touches only incident s-cliques per round) is preferred.
#
# TINY_NR / SHARD_MIN_INCIDENCE are the *static fallback* values (re-
# exported from ``planner_profile``): ``resolve_plan`` prefers the
# measured per-device crossovers of ``planner_profile.json`` (written by
# ``tools/calibrate_planner.py``) and records which source fired in the
# Plan reasons.
TINY_NR = planner_profile.STATIC_TINY_NR
SHARD_MIN_INCIDENCE = planner_profile.STATIC_SHARD_MIN_INCIDENCE
DENSE_ROUND_BYTES_PER_ENTRY = 12


@dataclasses.dataclass(frozen=True)
class Plan:
    """The planner's decision record: requested vs resolved + why.

    Attached to every ``Decomposition`` (explicit configs get a trivial
    plan) and embedded in ``to_json()`` so a served artifact still says
    how it was computed."""

    backend: str
    hierarchy: str
    requested_backend: str
    requested_hierarchy: str
    reasons: Tuple[str, ...] = ()
    # the dense engine's decrement kernel, resolved once here (True:
    # Pallas scatter plan, False: XLA scatter-add); None off the dense
    # backend.  ``api.plan_config`` hands it to the engine as
    # ``config.use_pallas``, so the report and the run cannot disagree.
    use_pallas: Optional[bool] = None

    @property
    def was_auto(self) -> bool:
        return AUTO in (self.requested_backend, self.requested_hierarchy)

    def report(self) -> str:
        """Human-readable resolution report (examples print this)."""
        lines = [
            f"plan: backend={self.backend!r} hierarchy={self.hierarchy!r}"
            f" (requested backend={self.requested_backend!r}"
            f" hierarchy={self.requested_hierarchy!r})"]
        lines += [f"  - {r}" for r in self.reasons]
        return "\n".join(lines)

    def to_dict(self) -> Dict[str, Any]:
        return {"backend": self.backend, "hierarchy": self.hierarchy,
                "requested_backend": self.requested_backend,
                "requested_hierarchy": self.requested_hierarchy,
                "reasons": list(self.reasons),
                "use_pallas": self.use_pallas}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Plan":
        missing = [k for k in ("backend", "hierarchy", "requested_backend",
                               "requested_hierarchy") if k not in d]
        if missing:
            raise ValueError(
                f"malformed Decomposition plan: missing {missing} in {d!r} "
                f"— the artifact was truncated or hand-edited; regenerate "
                f"it with to_json()/save()")
        return cls(backend=d["backend"], hierarchy=d["hierarchy"],
                   requested_backend=d["requested_backend"],
                   requested_hierarchy=d["requested_hierarchy"],
                   reasons=tuple(d.get("reasons", ())),
                   use_pallas=d.get("use_pallas"))


def candidate_backends(config) -> List[Backend]:
    """Registry entries whose capabilities satisfy every explicit axis of
    ``config`` (the planner chooses among these; registry order is the
    tiebreak order)."""
    out = []
    for b in all_backends():
        caps = b.capabilities
        if config.method not in caps.methods:
            continue
        if config.hierarchy != AUTO and \
                not _hierarchy_supported(caps, config.hierarchy):
            continue
        if any(is_set(config) and knob not in caps.knobs
               for knob, (is_set, _t) in _KNOB_RULES.items()):
            continue
        out.append(b)
    return out


def resolve_plan(config, *, n_r: int, n_s: int, n_sub: int,
                 platform: Optional[str] = None,
                 device_kind: Optional[str] = None,
                 n_devices: Optional[int] = None,
                 r: Optional[int] = None, s: Optional[int] = None,
                 profile_path: Optional[str] = None,
                 build: Optional[str] = None,
                 eager_build_bytes: Optional[int] = None) -> Plan:
    """Resolve ``backend='auto'`` / ``hierarchy='auto'`` to concrete axes.

    Problem facts come in as plain ints so the rules are unit-testable;
    ``decompose()``/``Session`` pass them from the built problem.  Device
    facts (``platform`` as ``jax.default_backend()`` names it,
    ``device_kind`` as ``jax.devices()[0].device_kind`` does, e.g. "TPU v5
    lite") default to this process's jax runtime.  The rules (priority
    order, DESIGN.md §8):

      1. an explicit backend is kept as-is;
      2. knobs bind: ``mesh``/``compress`` force the sharded collective,
         ``use_pallas`` the dense engine;
      2b. build facts bind (DESIGN.md §13): a sharded incidence build
         (``build='sharded'``), or an estimated eager build working set
         (``eager_build_bytes``) exceeding ``memory_budget_bytes``, on a
         multi-device host -> sharded (the peel partitions the very
         s-clique slabs the build produced);
      3. multi-device + enough incidence work (>= the shard crossover
         entries) -> sharded;
      4. a ``memory_budget_bytes`` smaller than the dense engine's
         per-round working set -> gather (work-efficient);
      5. accelerator -> dense (compiled engine);
      6. CPU: tiny problems (below the compile-vs-eager crossover) ->
         gather (no compile), everything else -> dense.

    The crossover thresholds of rules 3 and 6 come from the loaded
    ``planner_profile.json`` entry for this device kind (measured by
    ``tools/calibrate_planner.py``), falling back to the static
    ``TINY_NR``/``SHARD_MIN_INCIDENCE`` constants; the Plan reasons
    record which source fired.  ``profile_path`` overrides the profile
    location (tests).

    ``hierarchy='auto'`` then picks the richest strategy the resolved
    backend supports: fused > replay > two_phase.  When (r, s) = (1, 2)
    and the resolved backend declares the "kcore" fast lane, the reasons
    additionally record that the degenerate k-core case routes to the
    dedicated vertex-peel engine lane.
    """
    reasons: List[str] = []
    cands = candidate_backends(config)
    if not cands:
        check_capabilities(config)          # raises with the derived message
        raise ConfigError("no capability-compatible backend")  # unreachable
    cand_names = [b.name for b in cands]
    if platform is None or device_kind is None or n_devices is None:
        import jax
        platform = platform or jax.default_backend()
        device_kind = device_kind or jax.devices()[0].device_kind
        n_devices = n_devices if n_devices is not None \
            else len(jax.devices())

    if config.backend != AUTO:
        backend = config.backend
        reasons.append(f"backend {backend!r}: explicitly configured")
    else:
        prof = planner_profile.thresholds(device_kind=device_kind,
                                          platform=platform,
                                          path=profile_path)
        tiny_nr = prof["tiny_nr"]
        shard_min = prof["shard_min_incidence"]
        prof_src = prof["source"]
        reasons.append(
            f"thresholds: tiny_nr={tiny_nr}, "
            f"shard_min_incidence={shard_min} ({prof_src})")
        budget = config.memory_budget_bytes
        dense_round_bytes = DENSE_ROUND_BYTES_PER_ENTRY * n_s * n_sub

        def pick(name, why):
            if name in cand_names:
                reasons.append(f"backend {name!r}: {why}")
                return name
            return None

        backend = None
        if config.mesh is not None:
            backend = pick("sharded", "a mesh was supplied")
        if backend is None and config.compress:
            backend = pick("sharded",
                           "compress=True implies the sharded collective")
        if backend is None and config.use_pallas:
            backend = pick("dense", "use_pallas=True selects the dense "
                                    "engine's Pallas scatter plan")
        if backend is None and n_devices > 1 and build == "sharded":
            backend = pick(
                "sharded",
                f"the incidence structure was built sharded over "
                f"{n_devices} devices; the peel partitions the same "
                f"s-clique slabs")
        if backend is None and n_devices > 1 and budget is not None and \
                eager_build_bytes is not None and eager_build_bytes > budget:
            backend = pick(
                "sharded",
                f"estimated eager build working set ~{eager_build_bytes} B "
                f"exceeds memory_budget_bytes={budget} on {n_devices} "
                f"devices: shard the build and the peel together")
        if backend is None and n_devices > 1 and \
                n_s * n_sub >= shard_min:
            backend = pick(
                "sharded",
                f"{n_devices} devices and {n_s * n_sub} incidence entries "
                f">= {shard_min} ({prof_src}): partition the s-clique axis")
        if backend is None and budget is not None and \
                dense_round_bytes > budget:
            backend = pick(
                "gather",
                f"dense per-round working set ~{dense_round_bytes} B "
                f"exceeds memory_budget_bytes={budget}; the gather "
                f"backend touches only incident s-cliques per round")
        if backend is None and platform != "cpu":
            backend = pick("dense", f"accelerator ({device_kind}): the "
                                    f"compiled engine is the fast path")
        if backend is None and n_r < tiny_nr:
            backend = pick(
                "gather",
                f"tiny problem (n_r={n_r} < {tiny_nr}, {prof_src}) on "
                f"cpu: the eager work-efficient loop beats paying an XLA "
                f"compile")
        if backend is None:
            backend = pick("dense", f"cpu default (n_r={n_r}): the "
                                    f"compiled engine amortizes its "
                                    f"compile over the peel rounds")
        if backend is None:             # preferred pick filtered by caps
            backend = cand_names[0]
            reasons.append(
                f"backend {backend!r}: first capability-compatible "
                f"candidate (preferred picks excluded by the requested "
                f"method/hierarchy/knobs)")

    pallas = None
    if backend == "dense":
        if config.use_pallas is None:
            pallas = planner_profile.use_pallas_default(
                platform, device_kind, path=profile_path)
            why = "use_pallas=None resolved for this device"
        else:
            pallas, why = config.use_pallas, "explicitly configured"
        if pallas and n_s == 0:
            reasons.append(f"kernel: none, no s-cliques to scatter ({why})")
        elif pallas:
            reasons.append(
                f"kernel: Pallas scatter-only plan (sorted segment sum), "
                f"interpret={platform == 'cpu'} ({why})")
        else:
            reasons.append(f"kernel: XLA scatter-add ({why})")
    caps = get(backend).capabilities
    if (r, s) == (1, 2) and "kcore" in caps.fast_lanes:
        reasons.append(
            f"fast lane 'kcore': (r, s) = (1, 2) on backend {backend!r} — "
            f"vertex-degree peel with the one-shot edge-list link "
            f"fixpoint, no incidence-table indirection")
    if config.hierarchy != AUTO:
        hierarchy = config.hierarchy
        reasons.append(f"hierarchy {hierarchy!r}: explicitly configured")
    elif caps.compiled_peel:
        hierarchy = "fused"
        reasons.append("hierarchy 'fused': the resolved backend has a "
                       "compiled peel loop to fuse the LINK fixpoint into")
    elif caps.records_trace:
        hierarchy = "replay"
        reasons.append("hierarchy 'replay': the resolved backend records "
                       "the peel trace the host LINK replay consumes")
    else:
        hierarchy = "two_phase"
        reasons.append("hierarchy 'two_phase': the resolved backend "
                       "returns only core numbers, so the tree is built "
                       "by the two-phase (ANH-TE) post-pass")
    return Plan(backend=backend, hierarchy=hierarchy,
                requested_backend=config.backend,
                requested_hierarchy=config.hierarchy,
                reasons=tuple(reasons), use_pallas=pallas)


# ---------------------------------------------------------------------------
# The four in-tree backends, ported from decompose()'s dispatch chain.
# Implementations are imported lazily so this module stays import-light.
# ---------------------------------------------------------------------------

def _run_local(problem: NucleusProblem, config, backend: str,
               **peel_kw) -> BackendResult:
    from .peel import approx_coreness, exact_coreness
    fused = config.hierarchy == "fused"
    if config.method == "exact":
        res = exact_coreness(problem, backend=backend, hierarchy=fused,
                             **peel_kw)
    else:
        res = approx_coreness(problem, delta=config.delta, backend=backend,
                              hierarchy=fused, **peel_kw)
    return BackendResult(
        core=np.asarray(res.core), rounds=int(res.rounds),
        order_round=np.asarray(res.order_round),
        peel_value=np.asarray(res.peel_value),
        uf_parent=np.asarray(res.uf_parent) if fused else None,
        uf_L=np.asarray(res.uf_L) if fused else None)


def _run_dense(problem: NucleusProblem, config) -> BackendResult:
    return _run_local(problem, config, "dense", use_pallas=config.use_pallas)


def _run_gather(problem: NucleusProblem, config) -> BackendResult:
    return _run_local(problem, config, "gather")


def _run_sharded(problem: NucleusProblem, config) -> BackendResult:
    from .distributed import sharded_decomposition
    mesh = config.mesh
    if mesh is None:
        from ..launch.mesh import make_host_mesh
        mesh = make_host_mesh()
    fused = config.hierarchy == "fused"
    out = sharded_decomposition(problem, mesh, kind=config.method,
                                delta=config.delta, compress=config.compress,
                                hierarchy=fused)
    if fused:
        core, rounds, parent, L, raw = out
        return BackendResult(core=np.asarray(core), rounds=int(rounds),
                             peel_value=np.asarray(raw),
                             uf_parent=np.asarray(parent),
                             uf_L=np.asarray(L))
    return BackendResult(core=np.asarray(out[0]), rounds=int(out[1]))


def _run_nh(problem: NucleusProblem, config) -> BackendResult:
    from .nh_baseline import nh_coreness
    core, rho = nh_coreness(problem)
    return BackendResult(core=np.asarray(core), rounds=int(rho))


register(_Registered(
    name="dense",
    capabilities=BackendCapabilities(
        methods=("exact", "approx"), compiled_peel=True, records_trace=True,
        knobs=frozenset({"pallas"}),
        summary="the compiled single-device lax.while_loop engine",
        fast_lanes=("kcore",)),
    _run=_run_dense))

register(_Registered(
    name="gather",
    capabilities=BackendCapabilities(
        methods=("exact", "approx"), compiled_peel=False, records_trace=True,
        knobs=frozenset(),
        summary="the eager work-efficient host loop"),
    _run=_run_gather))

register(_Registered(
    name="sharded",
    capabilities=BackendCapabilities(
        methods=("exact", "approx"), compiled_peel=True, records_trace=False,
        knobs=frozenset({"mesh", "compress"}),
        summary="the shard_map distributed engine"),
    _run=_run_sharded))

register(_Registered(
    name="nh",
    capabilities=BackendCapabilities(
        methods=("exact",), compiled_peel=False, records_trace=False,
        knobs=frozenset(),
        summary="the sequential exact baseline; it has no approximate "
                "bucket schedule"),
    _run=_run_nh))

BACKENDS = names()
