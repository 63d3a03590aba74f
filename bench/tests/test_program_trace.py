"""The reduction of the program's spans, scopes and counters
(``program_trace.py``, ``xspace.py``, the readers that use them)."""
import gzip
import json
import os
import shutil
import types

import pytest

import program_trace as pt
import tracing
import xspace
from spec import load_module

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = os.path.join(os.path.dirname(DATA), "..", "metrics")
READERS = ("peel_round_ms", "fixpoint_share", "fixpoint_gens",
           "link_slot_use", "route_host_s", "padding_ratio")
FIX, UF, PEEL = "repro.link_fixpoint", "repro.uf_union", "repro.peel_round"


def unpack(tmp_path, name):
    path = tmp_path / name
    with gzip.open(os.path.join(DATA, name + ".gz")) as f, \
            open(path, "wb") as out:
        shutil.copyfileobj(f, out)
    return str(path)


def reader(name):
    return load_module(os.path.join(METRICS, name + ".py"),
                       "metric reader", "read").read


def test_xspace_reads_op_metadata(tmp_path):
    """On the recorded v5e trace: an op's tf_op and category, and a while
    op, which has a category and no tf_op."""
    meta = xspace.device_op_metadata(
        unpack(tmp_path, "tiny_coreness.xplane.pb"), "/device:TPU:")
    ops = {m.display_name: m for m in meta["/device:TPU:0"].values()}
    fusion = ops["subtract_clamp_fusion.2"]
    assert fusion.tf_op == "jit(_dense_engine)/while/body/min:"
    assert fusion.hlo_category == "loop fusion"
    assert fusion.name.startswith("%subtract_clamp_fusion.2 = ")
    assert ops["while.35"].hlo_category == "while"
    assert ops["while.35"].tf_op is None
    assert fusion.program_id == ops["while.35"].program_id


def test_scope_path():
    assert pt.scope_path(
        "jit(f)/while/body/repro.link_fixpoint/while/body/"
        "repro.uf_union/while/body/min:") == (FIX, UF)
    assert pt.scope_path("jit(f)/while/body/min:") == ()
    assert pt.scope_path(None) is None


def op(s, e, path, sync=True):
    return pt.Op(s, e, path, sync)


# the peel loop [0, 100): a peel-round op, then the fixpoint's while (no
# tf_op) holding its own ops, a copy the compiler inserted (no tf_op) and
# the union-find's while; an async copy runs past the loop's end
OPS = [op(0, 100, None),
       op(0, 10, (PEEL,)),
       op(10, 90, None),
       op(12, 20, (FIX,)),
       op(20, 25, None),
       op(25, 60, None),
       op(30, 50, (FIX, UF)),
       op(60, 80, (FIX,)),
       op(95, 98, ()),
       op(90, 120, (), sync=False)]


def test_scope_time_counts_each_instant_once():
    t = pt.plane_scope_time(OPS, [(0, 200)])
    # the fixpoint's while [10, 90) takes the fixpoint's path; its gaps
    # (10-12, 80-90), its copy (20-25) and its own ops count there; the
    # union-find's while (25-60 less 30-50) takes the union-find's path
    assert t == {(PEEL,): 10, (FIX,): 2 + 8 + 5 + 20 + 10,
                 (FIX, UF): 35, (): 5 + 2 + 3 + 20}
    assert sum(t.values()) == tracing.length([(o.start, o.end) for o in OPS])
    assert pt.time_under(t, FIX) == 80
    clipped = pt.plane_scope_time(OPS, [(5, 15), (95, 110)])
    assert clipped == {(PEEL,): 5, (FIX,): 2 + 3, (): 2 + 3 + 10}
    names = dict(pt.device_scopes(pt.ProgramTrace(
        ops={"/device:TPU:0": OPS}, spans=[]), 0, 200))
    assert names == pytest.approx(
        {PEEL: 10e-9, FIX: 45e-9, UF: 35e-9, "other": 30e-9})


def test_innermost_spans_and_idle_by_program_span():
    spans = [(0, 100, "repro.route"), (10, 90, "repro.session.decompose"),
             (20, 30, "repro.session.pad"), (40, 80, "repro.engine")]
    assert pt.innermost_spans(spans) == [
        (0, 10, "repro.route"), (10, 20, "repro.session.decompose"),
        (20, 30, "repro.session.pad"), (30, 40, "repro.session.decompose"),
        (40, 80, "repro.engine"), (80, 90, "repro.session.decompose"),
        (90, 100, "repro.route")]
    tr = tracing.Trace(
        ops={"/device:TPU:0": [(45, 75, "while.1")]},
        spans=[(-20, 130, "bench.window"), (-10, 0, "bench.build"),
               (0, 100, "bench.route")])
    prog = pt.ProgramTrace(ops={}, spans=[(s, e, n, {}) for s, e, n in spans])
    assert dict(pt.idle_by_program_span(tr, prog, -20, 130)) == pytest.approx({
        "outside": 40e-9, "bench.build": 10e-9, "repro.route": 20e-9,
        "repro.session.decompose": 30e-9, "repro.session.pad": 10e-9,
        "repro.engine": 10e-9})


def fake_run(trace):
    return types.SimpleNamespace(trace=trace)


def test_readers_on_spans_and_counters():
    ops = [op(110, 140, (PEEL,)), op(140, 180, None), op(145, 170, (FIX,)),
           op(210, 230, (PEEL,)), op(230, 240, (FIX,))]
    tr = tracing.Trace(ops={"/device:TPU:0": [(o.start, o.end, "x")
                                              for o in ops]},
                       spans=[(0, 500, "bench.window")])
    counts = {"rounds": 2, "fixpoint_gens": 6, "live_links": 30,
              "link_slots": 600, "n_r": 10, "n_s": 30, "n_r_pad": 16,
              "n_s_pad": 64}
    tr.program = pt.ProgramTrace(
        ops={"/device:TPU:0": ops},
        spans=[(100, 200, "repro.route", {}),
               (105, 190, "repro.engine", counts),
               (200, 300, "repro.route", {}),
               (205, 250, "repro.engine", counts)])
    run = fake_run(tr)
    got = {name: reader(name)(run) for name in READERS}
    assert got == pytest.approx({
        "peel_round_ms": 50e-6 / 4, "fixpoint_share": 100 * 50 / 100,
        "fixpoint_gens": 3.0, "link_slot_use": 5.0,
        "route_host_s": (15 + 55) / 2 / 1e9, "padding_ratio": 80 / 40})


def test_readers_find_nothing_without_the_program_spans(tmp_path):
    """A trace of a program with no spans or scopes (the recorded
    coreness run predates them): every reader returns None, and the
    loader leaves the harness's fields as they were."""
    path = unpack(tmp_path, "tiny_coreness.xplane.pb")
    tr = tracing.load(path)
    assert [n for _, _, n in tr.spans] == ["bench.window", "bench.build",
                                           "bench.route"]
    assert tr.program.spans == []
    assert sum(len(v) for v in tr.program.ops.values()) == sum(
        len(v) for v in tr.ops.values())
    run = fake_run(tr)
    assert {name: reader(name)(run) for name in READERS} == dict.fromkeys(
        READERS)
    assert all(reader(name)(fake_run(None)) is None for name in READERS)


def test_recorded_forest_trace(tmp_path):
    """A tiny traced forest run recorded on a v5e chip by the harness with
    the spans and scopes in the program: the readers give here what they
    gave there, the scopes are found, and the split of device time adds
    up to the busy time."""
    path = unpack(tmp_path, "tiny_forest.xplane.pb")
    with open(os.path.join(DATA, "tiny_forest.expected.json")) as f:
        want = json.load(f)
    tr = tracing.load(path)
    run = fake_run(tr)
    for name, value in want["metrics"].items():
        assert reader(name)(run) == pytest.approx(value, rel=1e-9), name
    # recorded before its reader was written: the three engine spans ran
    # the (256, 512) bucket for (n_r, n_s) = (156, 374), (154, 376),
    # (159, 342)
    assert reader("padding_ratio")(run) == pytest.approx(
        3 * (256 + 512) / (156 + 374 + 154 + 376 + 159 + 342), rel=1e-9)
    lo, hi = pt.window(tr)
    scopes = dict(pt.device_scopes(tr.program, lo, hi))
    assert {PEEL, FIX, UF, "repro.round_links"} <= set(scopes)
    busy = sum(tracing.device_busy(tr, lo, hi).values()) / 1e9
    assert sum(scopes.values()) == pytest.approx(busy, rel=1e-9)
    assert busy == pytest.approx(want["busy_s"], rel=1e-9)
    times, engine_busy = pt.engine_time(run)
    assert sum(times.values()) == pytest.approx(engine_busy, rel=1e-9)
    names = {n for _, _, n, _ in tr.program.spans}
    assert set(want["spans"]) == names
    rids = {a.get("rid") for _, _, n, a in tr.program.spans
            if n != "repro.build" and not n.startswith("repro.build.")}
    assert len(rids) == want["jobs"] and None not in rids
