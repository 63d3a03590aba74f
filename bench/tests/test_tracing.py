"""The reduction from a profiler trace to busy time and idle share."""
import gzip
import json
import os
import shutil

import pytest

import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlaps_and_drops_empty():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (3, 3), (6, 9)]) == [
        (0, 3), (5, 9)]


def test_busy_idle_and_gaps():
    busy = [(0, 2), (1, 3), (5, 6)]
    assert tracing.length(tracing.clip(busy, 0, 10)) == 4
    assert tracing.gaps(busy, 0, 10) == [(3, 5), (6, 10)]
    assert tracing.overlap(busy, [(2, 5.5)]) == 1.5


def test_op_name():
    assert tracing.op_name(
        "%fusion.170 = s32[513]{0} fusion(s32[6656]{0} %x)") == "fusion.170"


def test_idle_attributed_to_host_spans():
    tr = tracing.Trace(
        ops={"/device:TPU:0": [(0, 10, "a"), (20, 30, "b"), (30, 35, "a")]},
        spans=[(0, 40, "bench.window"), (8, 22, "bench.build"),
               (22, 40, "bench.route")])
    assert tracing.device_busy(tr, 0, 40) == {"/device:TPU:0": 25}
    assert tracing.top_ops(tr, 0, 40) == [("a", 15e-9), ("b", 10e-9)]
    # idle: 10-20 inside build; 35-40 inside route
    assert tracing.idle_by_host_span(tr, 0, 40) == [
        ("bench.build", 10e-9), ("bench.route", 5e-9)]
    assert tracing.busy_inside(tr, tracing.span_intervals(
        tr, "bench.route")) == 13


def test_recorded_chip_trace(tmp_path):
    """A trace recorded on a v5e chip by the harness itself; the reduction
    gives here what it gave there, and the idle time splits over the host
    spans without loss."""
    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(os.path.join(DATA, "tiny_coreness.xplane.pb.gz")) as f, \
            open(path, "wb") as out:
        shutil.copyfileobj(f, out)
    with open(os.path.join(DATA, "tiny_coreness.expected.json")) as f:
        want = json.load(f)
    tr = tracing.load(str(path))
    assert list(tr.ops) == [want["device"]]
    assert len(tr.ops[want["device"]]) == want["device_ops"]
    assert [n for _, _, n in tr.spans] == want["spans"]
    lo, hi = tracing.span_intervals(tr, "bench.window")[0]
    busy = tracing.device_busy(tr, lo, hi)[want["device"]]
    assert busy / 1e9 == pytest.approx(want["busy_s"], rel=1e-9)
    assert (hi - lo) / 1e9 == pytest.approx(want["window_s"], rel=1e-9)
    assert 100 * (1 - busy / (hi - lo)) == pytest.approx(
        want["device_idle.job"], rel=1e-9)
    # the union never exceeds the plain sum of op durations
    assert busy <= sum(min(e, hi) - max(s, lo)
                       for s, e, _ in tr.ops[want["device"]]
                       if e > lo and s < hi)
    idle = sum(s for _, s in tracing.idle_by_host_span(tr, lo, hi))
    assert idle == pytest.approx((hi - lo - busy) / 1e9, rel=1e-9)
