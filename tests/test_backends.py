"""Registry conformance (DESIGN.md §8).

Four contracts:
  * REGISTRY — the four in-tree backends are registered in the canonical
    order, their capability declarations reproduce the DESIGN.md §6
    legality matrix exactly (29 triples, same order — pinned literally),
    and ``validate()``'s derived errors name the offending backend.
  * CONFORMANCE — every registered backend's declared capabilities are
    exercised: each legal triple runs on a tiny graph and produces exactly
    the artifacts the capabilities promise (trace iff ``records_trace``,
    forest iff fused), with ``rounds`` always a python int; each illegal
    knob raises ``ConfigError`` naming the backend.
  * PLANNER — ``resolve_plan``'s decision rules, unit-tested on explicit
    device/problem facts.
  * AUTO PARITY — ``backend='auto'``/``hierarchy='auto'`` produce
    array-for-array the same decomposition as the explicitly-configured
    equivalent on every golden fixture, and the plan round-trips through
    JSON.
"""
import itertools
import json

import numpy as np
import pytest

from repro.core import backends as B
from repro.core import (ConfigError, Decomposition, NucleusConfig,
                        build_problem, decompose)
from repro.core.api import BACKENDS, HIERARCHIES, METHODS
from repro.graph.generators import golden_suite

pytestmark = pytest.mark.fast

# The DESIGN.md §6 matrix, pinned literally: legal_combinations() must emit
# exactly these triples in exactly this order (the byte-identity oracle the
# capability derivation is checked against).
EXPECTED_LEGAL = [
    ("exact", "dense", "none"), ("exact", "dense", "fused"),
    ("exact", "dense", "replay"), ("exact", "dense", "two_phase"),
    ("exact", "dense", "basic"),
    ("exact", "gather", "none"), ("exact", "gather", "replay"),
    ("exact", "gather", "two_phase"), ("exact", "gather", "basic"),
    ("exact", "sharded", "none"), ("exact", "sharded", "fused"),
    ("exact", "sharded", "two_phase"), ("exact", "sharded", "basic"),
    ("exact", "nh", "none"), ("exact", "nh", "two_phase"),
    ("exact", "nh", "basic"),
    ("approx", "dense", "none"), ("approx", "dense", "fused"),
    ("approx", "dense", "replay"), ("approx", "dense", "two_phase"),
    ("approx", "dense", "basic"),
    ("approx", "gather", "none"), ("approx", "gather", "replay"),
    ("approx", "gather", "two_phase"), ("approx", "gather", "basic"),
    ("approx", "sharded", "none"), ("approx", "sharded", "fused"),
    ("approx", "sharded", "two_phase"), ("approx", "sharded", "basic"),
]


@pytest.fixture(scope="module")
def problem():
    return build_problem(golden_suite()["two_triangles"](), 2, 3)


# ---------------------------------------------------------------------------
# Registry + derived legality
# ---------------------------------------------------------------------------

def test_registry_order_is_canonical():
    assert B.names() == ("dense", "gather", "sharded", "nh")
    assert BACKENDS == B.names()
    for b in B.all_backends():
        assert isinstance(b, B.Backend)


def test_legal_combinations_byte_identical():
    """The derived matrix == the pre-registry hand-coded matrix, same
    triples, same order."""
    assert NucleusConfig.legal_combinations() == EXPECTED_LEGAL


def test_capability_matrix_matches_design_table():
    want = {
        "dense": ("none", "fused", "replay", "two_phase", "basic"),
        "gather": ("none", "replay", "two_phase", "basic"),
        "sharded": ("none", "fused", "two_phase", "basic"),
        "nh": ("none", "two_phase", "basic"),
    }
    for name, hierarchies in want.items():
        assert B.get(name).capabilities.hierarchies == hierarchies
    assert B.get("nh").capabilities.methods == ("exact",)


def test_unknown_backend_lists_registered_and_auto():
    with pytest.raises(ConfigError, match="auto"):
        NucleusConfig(backend="cuda").validate()
    with pytest.raises(ConfigError, match="auto"):
        NucleusConfig(hierarchy="bogus").validate()


def test_illegal_knobs_name_the_backend():
    """Every derived error message names the offending backend (and the
    capability-compatible alternatives come from the registry)."""
    cases = [
        (dict(backend="gather", hierarchy="fused"), "gather"),
        (dict(backend="nh", hierarchy="fused"), "nh"),
        (dict(backend="sharded", hierarchy="replay"), "sharded"),
        (dict(backend="nh", hierarchy="replay"), "nh"),
        (dict(backend="nh", method="approx"), "nh"),
        (dict(backend="gather", use_pallas=True, hierarchy="none"), "gather"),
        (dict(backend="sharded", use_pallas=True, hierarchy="none"),
         "sharded"),
        (dict(backend="dense", compress=True), "dense"),
        (dict(backend="gather", compress=True, hierarchy="none"), "gather"),
        (dict(backend="dense", mesh=object()), "dense"),
        (dict(backend="nh", mesh=object(), hierarchy="none"), "nh"),
    ]
    for kwargs, name in cases:
        with pytest.raises(ConfigError) as ei:
            NucleusConfig(**kwargs).validate()
        assert name in str(ei.value), \
            f"{kwargs}: error must name backend {name!r}: {ei.value}"


def test_auto_with_unsatisfiable_knobs_is_config_error():
    # no registered backend honours pallas AND compress at once
    with pytest.raises(ConfigError, match="auto"):
        NucleusConfig(backend="auto", use_pallas=True,
                      compress=True).validate()


def test_register_rejects_duplicate_names():
    entry = B.get("dense")
    with pytest.raises(ValueError, match="already registered"):
        B.register(entry)


def test_runtime_registered_backend_is_live(problem):
    """The module contract: one register() call and validate(), the legal
    matrix and decompose() dispatch all follow — no snapshot staleness."""
    class _Oracle:
        name = "test_oracle"
        capabilities = B.BackendCapabilities(
            methods=("exact",), compiled_peel=False, records_trace=False,
            knobs=frozenset(), summary="a runtime-registered test backend")

        def run(self, prob, config):
            from repro.core.nh_baseline import nh_coreness
            core, rho = nh_coreness(prob)
            return B.BackendResult(core=np.asarray(core), rounds=int(rho))

    B.register(_Oracle())
    try:
        cfg = NucleusConfig(r=2, s=3, backend="test_oracle",
                            hierarchy="two_phase")
        cfg.validate()
        legal = NucleusConfig.legal_combinations()
        assert ("exact", "test_oracle", "two_phase") in legal
        assert len(legal) == 29 + 3  # none/two_phase/basic, exact-only
        dec = decompose(problem, cfg)
        ref = decompose(problem, NucleusConfig(r=2, s=3, backend="nh",
                                               hierarchy="two_phase"))
        np.testing.assert_array_equal(dec.core, ref.core)
        with pytest.raises(ConfigError, match="test_oracle"):
            NucleusConfig(backend="test_oracle", hierarchy="fused",
                          method="exact").validate()
    finally:
        del B._REGISTRY["test_oracle"]
    assert len(NucleusConfig.legal_combinations()) == 29


# ---------------------------------------------------------------------------
# Conformance: every declared capability is exercised
# ---------------------------------------------------------------------------

def _conformance_combo(problem, method, backend, hierarchy):
    caps = B.get(backend).capabilities
    dec = decompose(problem, NucleusConfig(
        r=2, s=3, method=method, backend=backend, hierarchy=hierarchy))
    label = f"{method}/{backend}/{hierarchy}"
    # rounds normalization: every backend adapter coerces (the old facade's
    # sharded+fused branch did not)
    assert type(dec.rounds) is int, label
    if caps.records_trace:
        assert dec.order_round is not None, label
        assert dec.peel_value is not None, label
    else:
        assert dec.order_round is None, label
    if hierarchy == "fused":
        assert caps.compiled_peel, label
        assert dec.uf_parent is not None and dec.uf_L is not None, label
    assert dec.plan is not None and not dec.plan.was_auto, label
    assert dec.plan.backend == backend, label


@pytest.mark.parametrize("method,backend,hierarchy", [
    pytest.param(m, b, h, id=f"{m}-{b}-{h}",
                 marks=[] if b != "sharded" else [pytest.mark.slow])
    for (m, b, h) in EXPECTED_LEGAL])
def test_conformance_every_legal_triple(problem, method, backend, hierarchy):
    _conformance_combo(problem, method, backend, hierarchy)


# ---------------------------------------------------------------------------
# Planner decision rules (explicit facts -> deterministic choices)
# ---------------------------------------------------------------------------

def _plan(cfg, *, n_r=1000, n_s=1000, n_sub=3, platform="cpu",
          device_kind=None, n_devices=1,
          profile_path="/nonexistent/planner_profile.json"):
    # profile_path defaults to a missing file so the decision-rule tests
    # exercise the static constants regardless of the committed profile;
    # a cpu device reports device_kind "cpu"
    return B.resolve_plan(cfg, n_r=n_r, n_s=n_s, n_sub=n_sub,
                          platform=platform,
                          device_kind=device_kind or platform,
                          n_devices=n_devices, profile_path=profile_path)


def test_planner_explicit_backend_is_kept():
    p = _plan(NucleusConfig(backend="gather", hierarchy="two_phase"))
    assert (p.backend, p.hierarchy) == ("gather", "two_phase")
    assert not p.was_auto


def test_planner_mesh_forces_sharded():
    p = _plan(NucleusConfig(backend="auto", mesh=object()))
    assert p.backend == "sharded" and p.was_auto


def test_planner_compress_forces_sharded():
    p = _plan(NucleusConfig(backend="auto", compress=True))
    assert p.backend == "sharded"


def test_planner_pallas_forces_dense():
    p = _plan(NucleusConfig(backend="auto", use_pallas=True))
    assert p.backend == "dense"


def test_planner_accelerator_prefers_dense():
    p = _plan(NucleusConfig(backend="auto"), platform="tpu",
              device_kind="TPU v5 lite")
    assert p.backend == "dense"
    # the TPU default kernel is the compiled Pallas scatter plan
    assert any("Pallas" in r and "interpret=False" in r for r in p.reasons)


def test_planner_cpu_tiny_prefers_gather_else_dense():
    assert _plan(NucleusConfig(backend="auto", hierarchy="auto"),
                 n_r=B.TINY_NR - 1).backend == "gather"
    assert _plan(NucleusConfig(backend="auto", hierarchy="auto"),
                 n_r=B.TINY_NR).backend == "dense"


def test_planner_multi_device_needs_enough_work():
    big = B.SHARD_MIN_INCIDENCE
    assert _plan(NucleusConfig(backend="auto"), n_devices=8,
                 n_s=big, n_sub=1).backend == "sharded"
    assert _plan(NucleusConfig(backend="auto"), n_devices=8,
                 n_s=1000, n_sub=3).backend == "dense"


def test_planner_memory_budget_steers_to_gather():
    cfg = NucleusConfig(backend="auto", hierarchy="auto", build="chunked",
                        memory_budget_bytes=1 << 10)
    p = _plan(cfg, n_s=100_000, n_sub=3)
    assert p.backend == "gather" and p.hierarchy == "replay"
    # the default hierarchy='fused' needs a compiled loop, which overrides
    # the budget preference (capability filter beats preference order)
    fused = NucleusConfig(backend="auto", build="chunked",
                          memory_budget_bytes=1 << 10)
    assert _plan(fused, n_s=100_000, n_sub=3).backend == "dense"


def test_planner_explicit_hierarchy_constrains_candidates():
    # fused needs a compiled peel: tiny-on-cpu may not fall back to gather
    p = _plan(NucleusConfig(backend="auto", hierarchy="fused"),
              n_r=B.TINY_NR - 1)
    assert p.backend == "dense"
    assert p.hierarchy == "fused"


def test_planner_hierarchy_auto_follows_capabilities():
    assert _plan(NucleusConfig(backend="dense",
                               hierarchy="auto")).hierarchy == "fused"
    assert _plan(NucleusConfig(backend="gather",
                               hierarchy="auto")).hierarchy == "replay"
    assert _plan(NucleusConfig(backend="nh",
                               hierarchy="auto")).hierarchy == "two_phase"
    assert _plan(NucleusConfig(backend="sharded",
                               hierarchy="auto")).hierarchy == "fused"


def test_plan_report_is_human_readable():
    p = _plan(NucleusConfig(backend="auto", hierarchy="auto"))
    rep = p.report()
    assert "backend='dense'" in rep and "requested backend='auto'" in rep
    assert any(line.startswith("  - ") for line in rep.splitlines())


# ---------------------------------------------------------------------------
# Telemetry-driven thresholds (planner_profile.json -> resolve_plan)
# ---------------------------------------------------------------------------

from repro.core import planner_profile as PP  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_profile_cache():
    PP.reset_cache()
    yield
    PP.reset_cache()


def _write_profile(tmp_path, profiles, name="prof.json"):
    path = tmp_path / name
    path.write_text(json.dumps({"format": PP.FORMAT, "version": PP.VERSION,
                                "profiles": profiles}))
    return str(path)


def test_profile_thresholds_drive_the_planner(tmp_path):
    """A measured tiny_nr crossover replaces the static constant, and the
    Plan reasons say which profile entry fired."""
    path = _write_profile(tmp_path, {"cpu": {"tiny_nr": 200}})
    cfg = NucleusConfig(backend="auto", hierarchy="auto")
    small = _plan(cfg, n_r=150, profile_path=path)
    assert small.backend == "gather"       # 150 < measured 200
    assert any("planner_profile['cpu']" in r for r in small.reasons)
    big = _plan(cfg, n_r=250, profile_path=path)
    assert big.backend == "dense"
    # the static constant would have said dense for n_r=150
    assert _plan(cfg, n_r=150).backend == "dense"


def test_profile_device_kind_beats_platform(tmp_path):
    path = _write_profile(tmp_path, {
        "TPU v4": {"tiny_nr": 10}, "tpu": {"tiny_nr": 99}})
    entry, source = PP.profile_entry(device_kind="TPU v4", platform="tpu",
                                     path=path)
    assert entry["tiny_nr"] == 10 and "TPU v4" in source


def test_planner_finds_the_device_kind_entry(tmp_path):
    """The planner looks the profile up by the real device kind
    ("TPU v5 lite"), not by the platform string."""
    path = _write_profile(tmp_path, {
        "TPU v5 lite": {"tiny_nr": 7, "pallas_default": False}})
    p = _plan(NucleusConfig(backend="auto"), platform="tpu",
              device_kind="TPU v5 lite", profile_path=path)
    assert any("planner_profile['TPU v5 lite']" in r for r in p.reasons)
    assert any("XLA scatter-add" in r for r in p.reasons)


def test_profile_per_key_fallback(tmp_path):
    """An entry that measured only one crossover keeps the static value
    for the other (shard_min_incidence is unmeasured on 1 device)."""
    path = _write_profile(tmp_path, {"cpu": {"tiny_nr": 33}})
    th = PP.thresholds(device_kind="cpu", path=path)
    assert th["tiny_nr"] == 33
    assert th["shard_min_incidence"] == PP.STATIC_SHARD_MIN_INCIDENCE


def test_profile_missing_is_silent_static(tmp_path):
    th = PP.thresholds(device_kind="cpu",
                       path=str(tmp_path / "never_written.json"))
    assert th["tiny_nr"] == PP.STATIC_TINY_NR
    assert th["source"] == "static defaults"


def test_profile_malformed_warns_once_then_static(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text("{not json")
    with pytest.warns(UserWarning, match="falling back to the static"):
        th = PP.thresholds(device_kind="cpu", path=str(path))
    assert th["tiny_nr"] == PP.STATIC_TINY_NR
    import warnings as W
    with W.catch_warnings():
        W.simplefilter("error")        # second load must NOT warn again
        th2 = PP.thresholds(device_kind="cpu", path=str(path))
    assert th2["source"] == "static defaults"
    # a wrong format sentinel is malformed too
    path2 = tmp_path / "wrong_format.json"
    path2.write_text(json.dumps({"format": "something-else", "profiles": {}}))
    with pytest.warns(UserWarning, match="falling back to the static"):
        assert PP.load_profile(str(path2)) is None


def test_pallas_default_from_profile(tmp_path):
    path = _write_profile(tmp_path, {"cpu": {"pallas_default": True},
                                     "tpu": {"tiny_nr": 5}})
    assert PP.pallas_default(platform="cpu", path=path) is True
    # entry exists but never measured the kernel race -> None + warn
    with pytest.warns(UserWarning, match="calibrate_planner"):
        assert PP.pallas_default(platform="tpu", path=path) is None
    with pytest.warns(UserWarning, match="calibrate_planner"):
        assert PP.pallas_default(platform="rocm", path=path) is None


def test_pallas_by_default_consults_the_profile(tmp_path, monkeypatch):
    """engine.pallas_by_default (the use_pallas=None oracle) follows the
    profile verdict when one covers this platform."""
    from repro.core import engine as engine_mod
    path = _write_profile(tmp_path, {
        "cpu": {"pallas_default": True}, "tpu": {"pallas_default": True}})
    monkeypatch.setattr(PP, "PROFILE_PATH", path)
    assert engine_mod.pallas_by_default() is True
    PP.reset_cache()
    path2 = _write_profile(tmp_path, {
        "cpu": {"pallas_default": False}, "tpu": {"pallas_default": False}},
        name="prof2.json")
    monkeypatch.setattr(PP, "PROFILE_PATH", path2)
    assert engine_mod.pallas_by_default() is False


def test_planner_records_kcore_fast_lane():
    p = _plan(NucleusConfig(backend="auto", hierarchy="auto"))
    assert not any("kcore" in r for r in p.reasons)   # r/s unknown
    p12 = B.resolve_plan(NucleusConfig(backend="auto", hierarchy="auto"),
                         n_r=1000, n_s=1000, n_sub=2, platform="cpu",
                         device_kind="cpu",
                         n_devices=1, r=1, s=2,
                         profile_path="/nonexistent/planner_profile.json")
    assert any("fast lane 'kcore'" in r for r in p12.reasons)
    p23 = B.resolve_plan(NucleusConfig(backend="auto", hierarchy="auto"),
                         n_r=1000, n_s=1000, n_sub=3, platform="cpu",
                         device_kind="cpu",
                         n_devices=1, r=2, s=3,
                         profile_path="/nonexistent/planner_profile.json")
    assert not any("kcore" in r for r in p23.reasons)


def test_committed_profile_is_loadable():
    """The shipped src/repro/core/planner_profile.json parses and covers
    the reference platform (cpu)."""
    blob = PP.load_profile()
    assert blob is not None, "committed planner_profile.json missing/bad"
    assert "cpu" in blob["profiles"]
    th = PP.thresholds(device_kind="cpu")
    assert "planner_profile" in th["source"]
    assert th["tiny_nr"] >= 1


# ---------------------------------------------------------------------------
# Auto-planner parity vs explicit configs over the golden fixtures
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gname", sorted(golden_suite()))
def test_auto_parity_golden_fixtures(gname):
    problem = build_problem(golden_suite()[gname](), 2, 3)
    if problem.n_r == 0:
        pytest.skip("no r-cliques")
    auto = decompose(problem, NucleusConfig(r=2, s=3, backend="auto",
                                            hierarchy="auto"))
    assert auto.plan is not None and auto.plan.was_auto
    explicit = decompose(problem, NucleusConfig(
        r=2, s=3, backend=auto.config.backend,
        hierarchy=auto.config.hierarchy))
    np.testing.assert_array_equal(auto.core, explicit.core)
    assert auto.rounds == explicit.rounds
    if auto.order_round is not None:
        np.testing.assert_array_equal(auto.order_round, explicit.order_round)
        np.testing.assert_array_equal(auto.peel_value, explicit.peel_value)
    if auto.has_hierarchy:
        np.testing.assert_array_equal(np.asarray(auto.tree.parent),
                                      np.asarray(explicit.tree.parent))
        np.testing.assert_array_equal(np.asarray(auto.tree.level),
                                      np.asarray(explicit.tree.level))


def test_auto_plan_rides_the_serialized_artifact():
    problem = build_problem(golden_suite()["planted40"](), 2, 3)
    dec = decompose(problem, NucleusConfig(r=2, s=3, backend="auto",
                                           hierarchy="auto"))
    loaded = Decomposition.from_json(dec.to_json())
    assert loaded.plan == dec.plan
    assert loaded.plan_report() == dec.plan_report()
    d = json.loads(dec.to_json())
    assert d["plan"]["requested_backend"] == "auto"
    assert d["config"]["backend"] == dec.plan.backend  # resolved, not auto
