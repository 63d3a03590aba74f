"""Cells, configurations, generators, traffic, loops and metrics are
found by name, from files; adding one takes only new files and new
entries."""
import json

import pytest

import run
from cells import FOREST, NAMES
from spec import CellError, load_cell


def test_committed_cells_load(tiny_root):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = load_cell(str(tiny_root), w["name"])
        assert cell.chips == w["chips"]
        assert cell.config["name"] == w["config"]
        assert {m["name"] for m in cell.end_to_end} >= {"job_s", "setup_s"}
        assert "setup_s" not in cell.readers
        assert set(cell.readers) == {
            m["name"] for m in cell.end_to_end + cell.per_layer} - {
            "setup_s"}
        assert hasattr(cell.loop, "Loop") and hasattr(cell.generator,
                                                      "graph")


def test_per_layer_metrics_follow_their_workloads(tiny_root):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        for name in NAMES:
            cell = load_cell(str(tiny_root), name)
            assert (m["name"] in cell.readers) == (
                name in m.get("workloads", NAMES))
    assert set(FOREST) == set(next(
        m for m in bench["per_layer"] if m["name"] == "tree_query_s"
    )["workloads"])


def test_unknown_cell_is_an_error(tiny_root):
    with pytest.raises(CellError):
        load_cell(str(tiny_root), "no-such-cell")


@pytest.mark.parametrize("key,value", [("loop", "no-such-loop"),
                                       ("generator", "no-such-generator")])
def test_unknown_loop_or_generator_is_an_error(tiny_root, key, value):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    w = bench["workloads"][0]
    if key == "loop":
        path = tiny_root / "bench" / "traffic" / (w["traffic"] + ".json")
        data = json.loads(path.read_text())
        data["loop"] = value
    else:
        c = next(c for c in bench["configs"] if c["name"] == w["config"])
        path = tiny_root / c["file"]
        data = json.loads(path.read_text())
        data["graph"]["generator"] = value
    path.write_text(json.dumps(data))
    with pytest.raises(CellError):
        load_cell(str(tiny_root), w["name"])


RING = '''
import numpy as np


def graph(params, rng):
    """A path on n vertices, its labels drawn from the seed."""
    n = int(params["n"])
    label = rng.permutation(n)
    edges = np.sort(np.stack([label[:-1], label[1:]], axis=1), axis=1)
    return n, edges[np.lexsort((edges[:, 1], edges[:, 0]))]
'''

COUNT = '''
import time


class Loop:
    """Counts a graph's edges on the device, again and again."""

    def __init__(self, cell, seed, clock, log):
        self.cell, self.seed = cell, seed
        self.attempted = self.failed = 0
        self.ends = []
        self.sums = []

    def setup(self):
        import jax.numpy as jnp
        from graphs import rng_for
        n, edges = self.cell.generator.graph(self.cell.config["graph"],
                                             rng_for(self.seed, 0))
        self.edges = jnp.asarray(edges)
        self.want = int(edges.shape[0])
        self.count()

    def count(self):
        return int((self.edges[:, 0] >= 0).sum())

    def window(self, seconds):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds or not self.sums:
            self.attempted += 1
            self.sums.append(self.count())
            self.ends.append(time.perf_counter())

    def check(self):
        bad = sum(s != self.want for s in self.sums)
        return {"count_mismatch": {"value": bad, "limit": 0}}
'''


def test_new_cell_is_new_files_and_entries(tiny_root):
    """A later change adds a configuration with its own generator, a mix
    with its own loop, an end-to-end metric and a per-layer metric by
    writing new files and appending entries; the harness finds them and
    runs the cell."""
    bench_dir = tiny_root / "bench"
    (bench_dir / "generators" / "ring.py").write_text(RING)
    (bench_dir / "loops" / "count.py").write_text(COUNT)
    (bench_dir / "configs" / "ring-64.json").write_text(json.dumps({
        "name": "ring-64", "graph": {"generator": "ring", "n": 64}}))
    (bench_dir / "traffic" / "tally.json").write_text(json.dumps({
        "loop": "count"}))
    (bench_dir / "metrics" / "counts_per_s.py").write_text(
        "def read(run):\n"
        "    n = len(run.loop.ends)\n"
        "    return n / (run.loop.ends[-1] - run.window_start)\n")
    (bench_dir / "metrics" / "counted.py").write_text(
        "def read(run):\n    return len(run.loop.sums)\n")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "ring-64", "source": "test",
                             "file": "bench/configs/ring-64.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "ring-64.tally", "config": "ring-64",
                               "traffic": "tally", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "counts_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["ring-64.tally"]})
    bench["per_layer"].append({"name": "counted", "unit": "count",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "device", "moves": "counts_per_s",
                               "workloads": ["ring-64.tally"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = load_cell(str(tiny_root), "ring-64.tally")
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "counts_per_s"}
    result = run.run(cell, 2**31 + 9, 0.2, False, require_tpu=False)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"setup_s", "counts_per_s"}
    assert result["metrics"]["counts_per_s"]["value"] > 0
    # the committed cells do not pick up the new metrics
    for name in NAMES:
        assert "counted" not in load_cell(str(tiny_root), name).readers
