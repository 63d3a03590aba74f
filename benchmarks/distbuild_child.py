"""Subprocess worker for the `distbuild` bench lane.

Two modes, each in a fresh process with ``XLA_FLAGS`` forcing the requested
host device count (the same idiom the sharded tests use):

  build      build ONE (graph, r, s) incidence structure with the sharded
             builder (``build='sharded'``) and print the same JSON record as
             ``benchmarks.build_child`` — wall_s / peak_delta_kb / masked /
             accounted_bytes / digest — plus the sharded ``build_stats``
             block (chunks_per_shard, skew, exchange_bytes).  The lane
             compares the digest against the eager build's: they must match
             bit-for-bit at every shard count.

  decompose  the over-budget end-to-end demo: run ``decompose()`` under
             ``backend='auto'`` with a ``memory_budget_bytes`` the eager
             build's estimated working set exceeds, so the resolver upgrades
             the build to 'sharded' and the plan peels on the same sharded
             slabs.  The record carries the estimate, the resolved
             build/backend, and a digest of the core array.

A fresh process per cell is the only honest way to compare high-water marks
across builder configs, and the only way to vary the forced device count.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import time

import numpy as np


def run_distbuild_child(root: str, graph: str, r: int, s: int, shards: int,
                        budget: int | None = None,
                        chunk_size: int | None = None,
                        mode: str = "build",
                        timeout: int = 1800) -> dict:
    """Launch this module in a fresh subprocess (with ``shards`` forced
    host devices) and parse its JSON record."""
    from benchmarks.common import child_env
    flags = os.environ.get("XLA_FLAGS", "")
    env = child_env(root, "distbuild", {"XLA_FLAGS": (
        f"{flags} --xla_force_host_platform_device_count={shards}").strip()})
    cmd = [sys.executable, "-m", "benchmarks.distbuild_child",
           "--graph", graph, "--r", str(r), "--s", str(s),
           "--shards", str(shards), "--mode", mode]
    if budget is not None:
        cmd += ["--budget", str(budget)]
    if chunk_size is not None:
        cmd += ["--chunk-size", str(chunk_size)]
    out = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                        text=True, check=True, timeout=timeout)
    return json.loads(out.stdout.strip().splitlines()[-1])


def _build_cell(args) -> dict:
    from benchmarks.build_child import _proc_status_kb, problem_digest
    from benchmarks.common import suite
    from repro.core.incidence import build_problem

    g = suite([args.graph])[args.graph]
    rss0 = _proc_status_kb("VmRSS")
    hwm0 = _proc_status_kb("VmHWM")
    t0 = time.perf_counter()
    problem = build_problem(g, args.r, args.s, build="sharded",
                            shards=args.shards,
                            memory_budget_bytes=args.budget,
                            chunk_size=args.chunk_size)
    wall = time.perf_counter() - t0
    hwm1 = _proc_status_kb("VmHWM")
    return {
        "graph": args.graph, "r": args.r, "s": args.s, "build": "sharded",
        "shards": args.shards, "budget": args.budget,
        "n_r": problem.n_r, "n_s": problem.n_s,
        "wall_s": wall,
        "peak_delta_kb": (hwm1 - rss0) if (hwm1 > 0 and rss0 > 0) else -1,
        "masked": bool(hwm1 > 0 and hwm1 == hwm0 and hwm0 > rss0),
        "accounted_bytes": int(
            problem.build_stats["peak_intermediate_bytes"]),
        "stats": problem.build_stats,
        "orientation": problem.orientation,
        "digest": problem_digest(problem),
    }


def _decompose_cell(args) -> dict:
    from benchmarks.common import suite
    from repro.core import NucleusConfig, decompose
    from repro.core.incidence import pick_rank
    from repro.distbuild import estimate_eager_build_bytes

    g = suite([args.graph])[args.graph]
    dg, _ = pick_rank(g)
    est = int(estimate_eager_build_bytes(dg, args.s))
    cfg = NucleusConfig(r=args.r, s=args.s, backend="auto",
                        memory_budget_bytes=args.budget)
    t0 = time.perf_counter()
    dec = decompose(g, cfg)
    wall = time.perf_counter() - t0
    stats = (dec.problem.build_stats or {}) if dec.problem is not None else {}
    core = np.ascontiguousarray(np.asarray(dec.core))
    return {
        "graph": args.graph, "r": args.r, "s": args.s, "mode": "decompose",
        "budget": args.budget, "est_eager_bytes": est,
        "build": stats.get("build"), "n_shards": stats.get("n_shards"),
        "skew": stats.get("skew"),
        "backend": None if dec.plan is None else dec.plan.backend,
        "wall_s": wall, "rounds": int(dec.rounds),
        "n_r": int(core.shape[0]), "core_max": int(core.max(initial=0)),
        "core_digest": hashlib.sha256(core.tobytes()).hexdigest(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", required=True,
                    help="benchmarks.common suite name")
    ap.add_argument("--r", type=int, required=True)
    ap.add_argument("--s", type=int, required=True)
    ap.add_argument("--shards", type=int, required=True,
                    help="shard count (launcher forces this many devices)")
    ap.add_argument("--budget", type=int, default=None,
                    help="memory_budget_bytes (planner chunk sizing; "
                         "decompose mode: the auto-upgrade threshold)")
    ap.add_argument("--chunk-size", type=int, default=None)
    ap.add_argument("--mode", default="build",
                    choices=["build", "decompose"])
    args = ap.parse_args()

    rec = _build_cell(args) if args.mode == "build" else _decompose_cell(args)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
