"""Subprocess worker for the `build` bench lane and `make bench-build`.

Builds ONE (graph, r, s) incidence structure with the requested builder in a
fresh process and prints a JSON record:

  wall_s            build wall-clock (graph generation excluded)
  peak_delta_kb     VmHWM after the build minus VmRSS right before it — the
                    build's own high-water contribution.  ``masked`` is true
                    when the import phase already peaked higher (the build
                    never moved the high-water mark), in which case
                    ``peak_delta_kb`` only bounds the build from above.
  accounted_bytes   the builder's own intermediate-memory meter
                    (``build_stats['peak_intermediate_bytes']``) —
                    deterministic, allocator-independent
  digest            SHA-256 over the five output arrays + orientation: the
                    bit-identity fingerprint the eager/chunked comparison
                    and the CI budget gate (tools/check_build_budget.py) use

A fresh process per cell is the only honest way to compare high-water marks
across builder configs.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import subprocess
import sys
import time

import numpy as np


def run_build_child(root: str, graph: str, r: int, s: int, build: str,
                    budget: int | None = None,
                    chunk_size: int | None = None,
                    timeout: int = 1200) -> dict:
    """Launch this module in a fresh subprocess and parse its JSON record.

    The one launcher shared by the `build` bench lane and the
    `make bench-build` CI gate (tools/check_build_budget.py)."""
    from benchmarks.common import child_env
    env = child_env(root, "build")
    cmd = [sys.executable, "-m", "benchmarks.build_child", "--graph", graph,
           "--r", str(r), "--s", str(s), "--build", build]
    if budget is not None:
        cmd += ["--budget", str(budget)]
    if chunk_size is not None:
        cmd += ["--chunk-size", str(chunk_size)]
    out = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                         text=True, check=True, timeout=timeout)
    return json.loads(out.stdout.strip().splitlines()[-1])


def _proc_status_kb(field: str) -> int:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith(field + ":"):
                    return int(line.split()[1])
    except OSError:
        pass
    if field == "VmHWM":  # some sandboxed kernels omit VmHWM; rusage has it
        import resource
        return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
    return -1


def problem_digest(problem) -> str:
    h = hashlib.sha256()
    for f in ("r_cliques", "inc_rid", "mem_offsets", "mem_sids", "deg0"):
        a = np.ascontiguousarray(np.asarray(getattr(problem, f)))
        h.update(f.encode())
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    h.update(problem.orientation.encode())
    return h.hexdigest()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", required=True, help="benchmarks.common suite name")
    ap.add_argument("--r", type=int, required=True)
    ap.add_argument("--s", type=int, required=True)
    ap.add_argument("--build", default="eager", choices=["eager", "chunked"])
    ap.add_argument("--budget", type=int, default=None,
                    help="memory_budget_bytes for build=chunked")
    ap.add_argument("--chunk-size", type=int, default=None)
    args = ap.parse_args()

    from benchmarks.common import suite
    from repro.core.incidence import build_problem

    g = suite([args.graph])[args.graph]
    kw = {}
    if args.build == "chunked":
        kw = {"memory_budget_bytes": args.budget,
              "chunk_size": args.chunk_size}

    rss0 = _proc_status_kb("VmRSS")
    hwm0 = _proc_status_kb("VmHWM")
    t0 = time.perf_counter()
    problem = build_problem(g, args.r, args.s, build=args.build, **kw)
    wall = time.perf_counter() - t0
    hwm1 = _proc_status_kb("VmHWM")

    print(json.dumps({
        "graph": args.graph, "r": args.r, "s": args.s, "build": args.build,
        "budget": args.budget, "n_r": problem.n_r, "n_s": problem.n_s,
        "wall_s": wall,
        "peak_delta_kb": (hwm1 - rss0) if (hwm1 > 0 and rss0 > 0) else -1,
        "masked": bool(hwm1 > 0 and hwm1 == hwm0 and hwm0 > rss0),
        "accounted_bytes": int(
            problem.build_stats["peak_intermediate_bytes"]),
        "stats": problem.build_stats,
        "orientation": problem.orientation,
        "digest": problem_digest(problem),
    }))


if __name__ == "__main__":
    main()
