"""Subprocess worker for the `server` bench lane (the restart claim).

Each mode runs in a FRESH process — cold in-memory jit caches are the
whole point — and times the server's first decompose on a PREBUILT
problem (the Session-lane convention: the build stage has its own lane,
the timer isolates what the warm path saves — compile + execute):

  cold   an empty persistent cache: a from-scratch server process,
         the baseline the restart claim is measured against.
  seed   the same cold first decompose, but into the cache the warm
         child reads, and the router's session manifest is saved to
         --cache-dir on exit — the "previous server run".
  warm   persistent cache + manifest: a restarted server.  The pools
         are pre-warmed from the manifest (all-ghost problems replay
         the exact jit keys, so XLA loads compiles from disk instead of
         building them), then the first real decompose is timed.

Prints one JSON record on stdout; `run_serve_child` is the launcher the
bench lane uses.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def run_serve_child(root: str, mode: str, xla_cache_dir: str,
                    manifest_dir: str = "", r: int = 2, s: int = 3,
                    timeout: int = 1200) -> dict:
    """Launch this module in a fresh subprocess whose persistent
    compilation cache is ``xla_cache_dir``; parse its JSON record."""
    from benchmarks.common import child_env
    env = child_env(root, "server",
                    {"JAX_COMPILATION_CACHE_DIR": xla_cache_dir})
    cmd = [sys.executable, "-m", "benchmarks.serve_child", "--mode", mode,
           "--r", str(r), "--s", str(s)]
    if manifest_dir:
        cmd += ["--cache-dir", manifest_dir]
    out = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                         text=True, check=True, timeout=timeout)
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", required=True,
                    choices=["cold", "seed", "warm"])
    ap.add_argument("--cache-dir", default="",
                    help="session manifest dir (seed/warm)")
    ap.add_argument("--r", type=int, default=2)
    ap.add_argument("--s", type=int, default=3)
    args = ap.parse_args()
    if args.mode in ("seed", "warm") and not args.cache_dir:
        raise SystemExit(f"--mode {args.mode} requires --cache-dir")

    from repro.core.incidence import build_problem
    from repro.graph import generators
    from repro.serve import (Request, Router, init_persistent_cache,
                             load_manifest, prewarm_router, save_manifest)

    # the selftest/warm-pool graph class: same shapes across modes, so
    # the warm child's manifest buckets match the graph it then serves
    g = generators.planted_cliques(120, [10, 8, 6], 0.03, seed=3)
    problem = build_problem(g, args.r, args.s)

    init_persistent_cache()
    router = Router()
    prewarm_s, prewarmed = 0.0, 0
    if args.cache_dir:
        os.makedirs(args.cache_dir, exist_ok=True)
    if args.mode == "warm":
        manifest = load_manifest(args.cache_dir)
        if manifest is None:
            raise SystemExit(
                f"no session manifest in {args.cache_dir}; run a seed "
                f"child first")
        t0 = time.perf_counter()
        prewarmed = prewarm_router(router, manifest)
        prewarm_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    dec = router.route(Request(graph=problem, r=args.r, s=args.s))
    wall = time.perf_counter() - t0

    if args.mode == "seed":
        save_manifest(router, args.cache_dir)

    stats = router.report()["pools"][0]["stats"]
    print(json.dumps({
        "mode": args.mode, "r": args.r, "s": args.s,
        "wall_s": wall, "prewarm_s": prewarm_s, "prewarmed": prewarmed,
        "warm": stats["warm"], "cold": stats["cold"],
        "n_r": dec.n_r,
        "kmax": int(dec.core.max()) if dec.n_r else 0,
    }))


if __name__ == "__main__":
    main()
