"""The committed cells, read from ``BENCHMARK.json``, for tests that run
each of them."""
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def committed():
    """[(cell name, its traffic mix)] of every committed cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = []
    for w in bench["workloads"]:
        with open(os.path.join(ROOT, "bench", "traffic",
                               w["traffic"] + ".json")) as f:
            out.append((w["name"], json.load(f)))
    return out


NAMES = [name for name, _ in committed()]
FOREST = [name for name, t in committed() if t.get("tree")]
