"""Makes the harness's modules and the program importable in its tests,
and gives them a tiny copy of the benchmark to run on the CPU."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# a scale small enough for the CPU; everything else as committed
TINY_SCALE = 6


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-shaped directory: the committed BENCHMARK.json and
    bench/ files, with each configuration's graphs cut to at most
    ``TINY_SCALE``."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(tmp_path / "BENCHMARK.json") as f:
        bench = json.load(f)
    for c in bench["configs"]:
        path = tmp_path / c["file"]
        cfg = json.loads(path.read_text())
        graph = cfg["graph"]
        if "scale" in graph:
            graph["scale"] = min(int(graph["scale"]), TINY_SCALE)
        path.write_text(json.dumps(cfg))
    return tmp_path
