"""Benchmark plumbing: timed runs + the standard graph suite.

The paper's SNAP graphs are offline; the suite substitutes synthetic graphs
with matched *structure* (power-law BA for social-like graphs, planted
cliques for nucleus-rich structure, ER for background) at CPU-tractable
scale.  Every benchmark prints `name,us_per_call,derived` CSV rows.
"""
from __future__ import annotations

import os
import time
from typing import Callable, Dict, Optional

import numpy as np

from repro.graph import generators, Graph

_SUITE: Dict[str, Callable[[], Graph]] = {
    "ba2k": lambda: generators.barabasi_albert(2_000, 8, seed=1),
    "er2k": lambda: generators.erdos_renyi_sparse(2_000, 16_000, seed=2),
    "planted1k": lambda: generators.planted_cliques(
        1_000, [24, 18, 14, 10], 0.01, seed=3),
    "ba4k": lambda: generators.barabasi_albert(4_000, 8, seed=7),
    "ba5k": lambda: generators.barabasi_albert(5_000, 6, seed=4),
    # nucleus-rich at build-bench scale: the planted 100-clique makes the
    # eager (2,4) expansion's intermediate candidate arrays ~100 MB — the
    # memory-headroom demo for the chunked incidence builder
    "planted3k": lambda: generators.planted_cliques(
        3_000, [100, 80, 60], 0.02, seed=5),
}

_CACHE: Dict[str, Graph] = {}


def suite(names=None) -> Dict[str, Graph]:
    names = names or list(_SUITE)
    for n in names:
        if n not in _CACHE:
            _CACHE[n] = _SUITE[n]()
    return {n: _CACHE[n] for n in names}


def timed(fn: Callable, repeats: int = 1, warmup: int = 0):
    for _ in range(warmup):
        fn()
    ts = []
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        ts.append(time.perf_counter() - t0)
    return out, min(ts)


def row(name: str, seconds: float, derived: str = "") -> str:
    return f"{name},{seconds * 1e6:.0f},{derived}"


def child_env(root: str, lane: str,
              extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Environment for a lane cell that runs in a child process.

    Such cells measure host-side quantities (peak RSS, restart compile)
    on the CPU.  A chip belongs to one process at a time, so a parent
    that holds it cannot hand it to a child, and a child must not fall
    back to the CPU on an accelerator host without being asked to.  The
    child therefore runs only where this process was pinned to the CPU
    with ``JAX_PLATFORMS=cpu``; anything else fails the lane here."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            f"lane {lane!r} runs its cells in child processes on the CPU; "
            f"run it with JAX_PLATFORMS=cpu (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r} here: a child cannot "
            f"share an accelerator with the process that holds it)")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.update(extra or {})
    return env
