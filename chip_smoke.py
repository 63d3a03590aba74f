#!/usr/bin/env python3
"""Chip smoke: decompose -> forest -> queries on one TPU chip.

    python chip_smoke.py [--seed 0] [--scale 10]
    python chip_smoke.py --four-chips [--seed 0] [--scale 10]

One process drives the exact (2,3) nucleus path through the entry points a
user calls, on ``jax.devices()[0]`` with the kernel the planner picks for a
TPU (the compiled Pallas scatter plan):

  1. a Graph500 Kronecker graph (``generators.kronecker``, edge factor 16)
     made from ``--seed``, and its (2,3) incidence built on the host;
  2. ``decompose(..., NucleusConfig(r=2, s=3, method="exact",
     hierarchy="fused"))``, then ``.tree``, then ``.cut(c)`` / ``.nuclei(c)``
     at a few levels;
  3. one decompose of the same graph through ``serve.Router`` (the
     ``Session`` shape-bucketed path the server compiles).

The default scale, 10, is the largest that finishes inside the run's time
limit, not what the chip's memory holds: the exact fused-hierarchy peel
makes every round a pass of random gathers and scatters over the whole
incidence (and every link generation one over the whole link worklist),
so its time grows with rounds x incidence.  Scale 16 (0.9 M r-cliques,
15.7 M s-cliques) compiles and fits the chip (``tests/test_tpu_compile.py``)
but does not finish in the limit; PERF.md has the measurements.

Correctness, each a hard failure:

  * core numbers, peel trace, round count and join forest equal those of
    the XLA scatter-add path (``use_pallas=False``) on the same chip and
    graph, and so does the canonical c-nucleus partition at every core
    level; the Router's result matches as well;
  * the graph (or, above scale ``REF_SCALE``, a scale-``REF_SCALE`` one)
    matches the sequential NH reference (``core.nh_baseline``, numpy only)
    exactly: core numbers and every level's partition;
  * every golden fixture under ``tests/golden/`` matches (one decompose per
    (r, s) over the disjoint union of the fixtures' graphs).

``--four-chips`` runs only the sharded path: ``decompose(...,
backend="sharded", hierarchy="fused")`` on a four-device mesh against the
single-device dense result.  It prints the device of each shard of the
incidence array the sharded run read, and checks from each device's
memory statistics that every device held its slab.

Earlier lines report sizes, seconds per phase (phases run one after
another, each ended by ``block_until_ready``; compile seconds come from
jax's own compile events), rounds, peak device memory and the plan.  The last
line is exactly ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}``.  With no TPU, on any mismatch and on any exception the
script exits non-zero and prints no result line.  It never falls back to
the CPU.  The compile cache follows ``serve.cache.init_persistent_cache``
(``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_SCALE = 10
# the largest Kronecker scale whose sequential NH reference finishes well
# inside a minute on one host core (scale 13: ~0.1 M r-cliques, ~30 s)
REF_SCALE = 13
# lowering to StableHLO and the XLA compile (or the persistent-cache load);
# jaxpr tracing is left out because nested jits report nested trace spans
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class SmokeFailure(Exception):
    """A correctness check or a precondition failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    log(f"check ok: {what}")


class CompileClock:
    """Seconds jax spends lowering and compiling (or loading from the
    persistent cache), read from jax's own monitoring events."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.total += duration


def timed(clock, label: str, fn):
    """Run ``fn`` to a result ended by block_until_ready and log its wall
    and compile seconds; peel_s is the rest (engine run, plan build and
    host<->device copies)."""
    import jax
    c0, t0 = clock.total, time.perf_counter()
    out = fn()
    jax.block_until_ready(out.core)
    wall = time.perf_counter() - t0
    comp = clock.total - c0
    log(f"{label}: wall_s={wall:.3f} compile_s={comp:.3f} "
        f"peel_s={wall - comp:.3f} rounds={out.rounds}")
    return out


def levels(core):
    import numpy as np
    return [int(c) for c in np.unique(np.asarray(core)) if c > 0]


def same_partitions(a, b, cs) -> bool:
    """Canonical c-nucleus partitions equal at every level in ``cs`` (the
    form the golden fixtures store)."""
    import numpy as np
    from repro.core import canonicalize_labels
    return all(np.array_equal(canonicalize_labels(a(c)),
                              canonicalize_labels(b(c))) for c in cs)


def build(g, label: str):
    import jax
    from repro.core import build_problem
    t0 = time.perf_counter()
    problem = build_problem(g, 2, 3, build="chunked")
    jax.block_until_ready((problem.inc_rid, problem.deg0, problem.mem_sids))
    log(f"{label}: n={g.n} m={g.m} n_r={problem.n_r} n_s={problem.n_s} "
        f"incidence_bytes={problem.inc_rid.nbytes} "
        f"build_s={time.perf_counter() - t0:.3f}")
    return problem


def forests_equal(a, b) -> bool:
    import numpy as np
    return (a.rounds == b.rounds and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in (
            (a.core, b.core), (a.order_round, b.order_round),
            (a.uf_parent, b.uf_parent), (a.uf_L, b.uf_L))))


def one_chip(args, clock, dev) -> None:
    import numpy as np
    from repro.core import NucleusConfig, decompose
    from repro.graph import generators
    from repro.graph.generators import GOLDEN_RS
    from repro.serve import Request, Router

    cfg = NucleusConfig(r=2, s=3, method="exact", hierarchy="fused")
    g = generators.kronecker(args.scale, edge_factor=16, seed=args.seed)
    problem = build(g, f"kronecker scale={args.scale} seed={args.seed}")

    dec = timed(clock, "decompose[default plan]",
                lambda: decompose(problem, cfg))
    log(f"peak_bytes_in_use={dev.memory_stats()['peak_bytes_in_use']}")
    report = dec.plan_report()
    log(report)
    # the engine runs config.use_pallas, which plan_config set from the
    # plan that the report line describes
    check(dec.plan.use_pallas is True and dec.config.use_pallas is True
          and "interpret=False" in report,
          "the plan ran the compiled Pallas path (interpret=False)")

    t0 = time.perf_counter()
    tree = dec.tree
    log(f"tree: nodes={tree.n_nodes} "
        f"tree_s={time.perf_counter() - t0:.3f}")
    cs = levels(dec.core)
    kmax = cs[-1]
    for c in sorted({cs[0], kmax // 4, kmax // 2, kmax} - {0}):
        t0 = time.perf_counter()
        labels = dec.cut(c)
        nuclei = dec.nuclei(c)
        dens = max((n.density for n in nuclei.values()
                    if n.density == n.density), default=0.0)
        log(f"query c={c}: members={int((labels >= 0).sum())} "
            f"nuclei={len(nuclei)} max_density={dens:.4f} "
            f"query_s={time.perf_counter() - t0:.3f}")

    ref = timed(clock, "decompose[xla scatter-add]",
                lambda: decompose(problem, NucleusConfig(
                    r=2, s=3, method="exact", hierarchy="fused",
                    use_pallas=False)))
    check(forests_equal(dec, ref),
          "Pallas vs XLA path: core, trace, rounds and forest identical")
    check(same_partitions(dec.cut, ref.cut, cs),
          f"Pallas vs XLA path: partitions equal at all {len(cs)} levels")
    del ref
    routed = timed(clock, "router[build+decompose]",
                   lambda: Router().route(Request(graph=g, r=2, s=3,
                                                  build="chunked")))
    check(np.array_equal(routed.core, dec.core)
          and same_partitions(routed.cut, dec.cut, cs),
          "Router (Session bucketed path) matches decompose()")
    del routed
    fixtures = golden_fixtures()
    for r, s in GOLDEN_RS:
        golden_group(clock, r, s, fixtures)
    check(True, f"all {len(fixtures)} golden fixtures match")
    if args.scale > REF_SCALE:
        del dec, problem
        g = generators.kronecker(REF_SCALE, edge_factor=16, seed=args.seed)
        problem = build(g, f"reference kronecker scale={REF_SCALE} "
                           f"seed={args.seed}")
        dec = timed(clock, "decompose[reference graph]",
                    lambda: decompose(problem, cfg))
    reference_check(problem, dec)


def reference_check(problem, dec) -> None:
    """``dec`` (a decompose of ``problem`` on the chip) against the
    sequential NH reference: core numbers and every level's partition."""
    import numpy as np
    from repro.core.nh_baseline import nh_full
    from repro.core.nuclei import cut_hierarchy

    t0 = time.perf_counter()
    core, tree, _ = nh_full(problem)
    log(f"nh reference: n_r={problem.n_r} "
        f"nh_s={time.perf_counter() - t0:.3f}")
    check(np.array_equal(np.asarray(dec.core), core),
          "core numbers equal the NH reference")
    cs = levels(core)
    check(same_partitions(dec.cut, lambda c: cut_hierarchy(tree, c), cs),
          f"partitions equal the NH reference at all {len(cs)} levels")


def golden_fixtures():
    from repro.graph.generators import GOLDEN_RS, golden_suite
    gdir = os.path.join(HERE, "tests", "golden")
    fixtures = []
    for fname in sorted(os.listdir(gdir)):
        if fname.endswith(".json"):
            with open(os.path.join(gdir, fname)) as f:
                fixtures.append((fname, json.load(f)))
    check(len(fixtures) == len(golden_suite()) * len(GOLDEN_RS),
          f"{len(fixtures)} golden fixtures found under {gdir}")
    return fixtures


def golden_group(clock, r: int, s: int, fixtures) -> None:
    """The (r, s) golden fixtures, in one decompose: the fixtures' graphs
    are joined into one disjoint union.  Nuclei never span components, so
    each fixture's core numbers and partitions are its slice of the
    union's; and since r-clique rows are lexsorted and each graph's
    vertex ids are offset past the previous one's, a fixture's r-cliques
    are a contiguous block of the union's, in its own order."""
    import numpy as np
    from repro.core import (NucleusConfig, build_problem,
                            canonicalize_labels, decompose)
    from repro.graph import make_graph
    from repro.graph.generators import golden_suite

    graphs = golden_suite()
    group = [(n, fx) for n, fx in fixtures if (fx["r"], fx["s"]) == (r, s)]
    edges, n = [], 0
    for _, fx in group:
        g = graphs[fx["graph"]]()
        edges.append(np.asarray(g.edges).reshape(-1, 2) + n)
        n += g.n
    problem = build_problem(make_graph(n, np.concatenate(edges)), r, s,
                            build="chunked")
    if problem.n_r != sum(fx["n_r"] for _, fx in group):
        raise SmokeFailure(f"golden r{r}s{s}: the union's r-clique count "
                           f"differs from the fixtures' sum")
    dec = timed(clock, f"golden[r{r}s{s}, {len(group)} fixtures]",
                lambda: decompose(problem, NucleusConfig(
                    r=r, s=s, hierarchy="fused")))
    core = np.asarray(dec.core)
    start = 0
    for fname, fx in group:
        sl = slice(start, start + fx["n_r"])
        start += fx["n_r"]
        if not np.array_equal(core[sl], np.asarray(fx["core"])):
            raise SmokeFailure(f"golden {fname}: core numbers differ")
        for c, want in fx["partitions"].items():
            got = canonicalize_labels(dec.cut(int(c))[sl])
            if not np.array_equal(got, np.asarray(want)):
                raise SmokeFailure(f"golden {fname}: partition c={c} "
                                   f"differs")


def four_chips(args, clock) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import NucleusConfig, decompose
    from repro.core.distributed import sharded_incidence
    from repro.graph import generators

    devs = jax.devices()
    check(len(devs) == 4, f"four devices present (found {len(devs)})")
    g = generators.kronecker(args.scale, edge_factor=16, seed=args.seed)
    problem = build(g, f"kronecker scale={args.scale} seed={args.seed}")
    # the sharded run goes first, so the memory it leaves is its own
    used0 = [d.memory_stats()["bytes_in_use"] for d in devs]
    mesh = Mesh(np.array(devs), ("chips",))
    dec = timed(clock, "decompose[sharded, 4 devices]",
                lambda: decompose(problem, NucleusConfig(
                    r=2, s=3, method="exact", hierarchy="fused",
                    backend="sharded", mesh=mesh)))
    log(dec.plan_report())
    # the incidence array the sharded run read (memoized on the problem)
    inc = sharded_incidence(problem, mesh)
    for sh in inc.addressable_shards:
        log(f"incidence shard rows={sh.index[0]} on {sh.device}")
    check(len({sh.device.id for sh in inc.addressable_shards}) == 4,
          "incidence shards live on four distinct devices")
    slab = inc.nbytes // len(devs)
    used = [d.memory_stats()["bytes_in_use"] for d in devs]
    for d, before, after in zip(devs, used0, used):
        log(f"{d}: bytes_in_use before={before} after={after} "
            f"peak_bytes_in_use={d.memory_stats()['peak_bytes_in_use']} "
            f"slab_bytes={slab}")
    check(all(a - b >= slab for b, a in zip(used0, used)),
          "every device holds its incidence slab after the sharded run")
    ref = timed(clock, "decompose[dense, one device]",
                lambda: decompose(problem, NucleusConfig(
                    r=2, s=3, method="exact", hierarchy="fused")))
    check(dec.rounds == ref.rounds
          and np.array_equal(dec.core, ref.core)
          and np.array_equal(np.asarray(dec.uf_parent),
                             np.asarray(ref.uf_parent))
          and np.array_equal(np.asarray(dec.uf_L), np.asarray(ref.uf_L)),
          "sharded vs single device: core, rounds and forest identical")
    cs = levels(ref.core)
    check(same_partitions(dec.cut, ref.cut, cs),
          f"sharded vs single device: partitions equal at all "
          f"{len(cs)} levels")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=DEFAULT_SCALE,
                    help="Kronecker scale: 2**scale vertices")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a 4-device mesh")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        print(f"chip_smoke: the repro package is not beside {__file__}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform} devices); "
              f"this smoke runs on the chip only", file=sys.stderr)
        return 3
    from repro.serve.cache import init_persistent_cache
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"compile cache: {init_persistent_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(args, clock)
        else:
            one_chip(args, clock, dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total_s={time.perf_counter() - t0:.3f} "
        f"compile_s={clock.total:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
