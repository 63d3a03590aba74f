"""Serving driver: batched LM decode + DIN CTR scoring + nucleus queries.

`python -m repro.launch.serve --arch minicpm-2b` prefills a batch of prompts
and decodes tokens with the KV cache; `--arch din` scores batched CTR
requests.  Request batching is continuous-style: a fixed-slot batch where
finished sequences are replaced by queued prompts every step (the static
shape keeps the step jit-stable).

`--arch nucleus` is the paper's build-once/query-many lane: it loads a
serialized ``Decomposition`` (``--decomposition path.json``, e.g. computed
offline by the sharded backend; without a path a small graph is decomposed,
serialized, and reloaded to prove the loop) and answers batched
``cut``/``nuclei`` queries with latency stats — the heavy-traffic story of
Fig. 10 end-to-end.  ``--warm-pool`` drives a stream of graphs through the
plan-aware ``repro.serve.Router`` (``--r/--s/--method`` accept comma lists,
so the pool exercises mixed tenant configs across per-config Sessions).
``--server`` starts the real multi-tenant front end (DESIGN.md §11): the
bounded-queue ``Frontend`` + stdlib HTTP surface, with ``--cache-dir``
holding the session manifest so a restarted server pre-warms its pools
from the persistent compilation cache; ``--selftest`` drives a short mixed workload
over HTTP (decompose + query + update + status) and exits — the CI smoke.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from functools import lru_cache

from ..configs import get_arch
from ..models import transformer as T
from ..models import din as DIN
from . import steps as S


# Step factories are memoized at module level (nucleuslint NL201): building
# `jax.jit(partial(step, cfg=...))` inside the driver body made every driver
# invocation re-trace the step — the same hazard class
# core/distributed._jitted_decomposition fixed for the sharded callable.
# The configs are frozen dataclasses, so they key an lru_cache directly.

@lru_cache(maxsize=16)
def _decode_step_fn(cfg):
    return jax.jit(partial(S.lm_decode_step, cfg=cfg))


@lru_cache(maxsize=16)
def _din_serve_step_fn(cfg):
    return jax.jit(partial(S.din_serve_step, cfg=cfg))


def serve_lm(arch_id: str, n_requests: int = 16, batch_slots: int = 4,
             prompt_len: int = 16, gen_len: int = 24, smoke: bool = True,
             quiet: bool = False):
    spec = get_arch(arch_id)
    cfg = spec.make_smoke_config() if smoke else spec.make_config()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    max_len = prompt_len + gen_len
    rng = np.random.default_rng(0)
    queue: List[np.ndarray] = [
        rng.integers(0, cfg.vocab, prompt_len).astype(np.int32)
        for _ in range(n_requests)]
    decode = _decode_step_fn(cfg)

    cache = T.init_cache(cfg, batch_slots, max_len)
    # slot state (host): current length per slot, tokens emitted
    slot_req = [-1] * batch_slots
    produced = {}
    done = 0
    # simple continuous batching loop: one token per step for all slots
    lens = jnp.zeros((), jnp.int32)
    # per-slot caches must share cache_len in this compact driver, so slots
    # are refilled in waves (wave = batch_slots requests)
    t0 = time.time()
    wave = 0
    while done < n_requests:
        take = queue[wave * batch_slots:(wave + 1) * batch_slots]
        if not take:
            break
        bs = len(take)
        toks = np.stack([np.pad(t, (0, prompt_len - len(t))) for t in take])
        cache = T.init_cache(cfg, bs, max_len)
        # prefill via decode steps over the prompt (simple + exact)
        cache_len = jnp.zeros((), jnp.int32)
        last = None
        for i in range(prompt_len):
            last, cache, cache_len = decode(params,
                                            jnp.asarray(toks[:, i:i + 1]),
                                            cache, cache_len)
        outs = [last]
        for _ in range(gen_len - 1):
            nxt, cache, cache_len = decode(params, outs[-1][:, None], cache,
                                           cache_len)
            outs.append(nxt)
        for bi, req in enumerate(take):
            produced[wave * batch_slots + bi] = np.stack(
                [np.asarray(o[bi]) for o in outs])
        done += bs
        wave += 1
    dt = time.time() - t0
    if not quiet:
        tput = done * gen_len / dt
        print(f"served {done} requests, {gen_len} tokens each, "
              f"{tput:.1f} tok/s")
    return produced


def serve_din(n_batches: int = 8, batch: int = 512, smoke: bool = True,
              quiet: bool = False):
    spec = get_arch("din")
    cfg = spec.make_smoke_config() if smoke else spec.make_config()
    params = DIN.init_params(jax.random.PRNGKey(0), cfg)
    from ..data import RecsysStream, RecsysStreamConfig
    stream = RecsysStream(RecsysStreamConfig(
        n_items=cfg.n_items, n_cates=cfg.n_cates, n_users=cfg.n_user_feats,
        seq_len=cfg.seq_len, batch=batch))
    step = _din_serve_step_fn(cfg)
    t0 = time.time()
    scores = []
    for i in range(n_batches):
        b = jax.tree.map(jnp.asarray, stream.batch(i))
        b.pop("label")
        scores.append(np.asarray(step(params, b)))
    dt = time.time() - t0
    if not quiet:
        print(f"scored {n_batches * batch} requests in {dt:.2f}s "
              f"({n_batches * batch / dt:.0f} req/s)")
    return np.concatenate(scores)


def _parse_pool_configs(r: str, s: str, method: str
                        ) -> List[Tuple[int, int, str]]:
    """Comma-list flag values -> positional (r, s, method) tuples (length-1
    lists broadcast), validated so a bad pair fails at the CLI, not three
    layers down."""
    rs = [int(x) for x in str(r).split(",")]
    ss = [int(x) for x in str(s).split(",")]
    ms = [m.strip() for m in str(method).split(",")]
    width = max(len(rs), len(ss), len(ms))
    bcast = lambda xs: xs * width if len(xs) == 1 else xs
    rs, ss, ms = bcast(rs), bcast(ss), bcast(ms)
    if not len(rs) == len(ss) == len(ms):
        raise SystemExit(
            f"--r/--s/--method comma lists must broadcast to one length; "
            f"got {len(rs)}/{len(ss)}/{len(ms)}")
    for rr, sv in zip(rs, ss):
        if not 1 <= rr < sv:
            raise SystemExit(f"need 1 <= r < s, got ({rr}, {sv})")
    return list(zip(rs, ss, ms))


def serve_nucleus_warm_pool(n_graphs: int = 5, n_queries: int = 32,
                            seed: int = 0, bucket_cap: int = 0,
                            r: str = "2", s: str = "3",
                            method: str = "exact",
                            quiet: bool = False):
    """Warm-pool serving through the plan-aware router.

    The heavy-traffic shape of the decompose-once/query-many story: many
    tenants submit similar-sized graphs under (possibly mixed) configs;
    the ``repro.serve.Router`` keys a ``Session`` pool per canonical
    config, so same-config same-bucket graphs reuse one compiled peel
    executable, and each resulting artifact then answers cut/nuclei
    queries.  ``--r/--s/--method`` accept comma lists — graphs
    round-robin over the config tuples, exercising multi-pool routing.
    Prints per-graph decompose latency (the cold-vs-warm split), the
    per-pool hit rates, and aggregate query latency.  Returns a stats
    dict (query percentiles are None when ``n_queries == 0`` — zero
    served queries have no latency distribution).
    """
    from ..core.incidence import build_problem
    from ..graph import generators
    from ..serve import Request, Router

    if n_graphs < 1:
        raise SystemExit("--pool-graphs must be >= 1")
    configs = _parse_pool_configs(r, s, method)
    router = Router(**({"bucket_cap": bucket_cap} if bucket_cap else {}))
    rng = np.random.default_rng(seed)
    dec_s: List[float] = []
    lat_us: List[float] = []
    queries = 0
    # the incidence structures are built up front (the build stage has its
    # own lane/chunked story, DESIGN.md §7); the timer below isolates what
    # the Sessions warm — the compiled peel + hierarchy
    requests = []
    for gi in range(n_graphs):
        # sizes drift but stay inside one power-of-two shape class, so the
        # pool demonstrates the warm path rather than bucket churn
        g = generators.planted_cliques(118 + 2 * gi, [10, 8, 6], 0.03,
                                       seed=seed + gi)
        rr, sv, mm = configs[gi % len(configs)]
        requests.append(Request(graph=build_problem(g, rr, sv),
                                r=rr, s=sv, method=mm))
    for req in requests:
        t0 = time.perf_counter()
        dec = router.route(req)
        dec_s.append(time.perf_counter() - t0)
        kmax = int(dec.core.max()) if dec.n_r else 0
        for c in rng.integers(1, max(kmax, 1) + 1, size=n_queries):
            t0 = time.perf_counter()
            dec.nuclei(int(c)) if queries % 2 else dec.cut(int(c))
            lat_us.append((time.perf_counter() - t0) * 1e6)
            queries += 1
    report = router.report()
    pools = report["pools"]
    warm_hits = sum(p["stats"]["warm"] for p in pools)
    n_buckets = sum(len(p["buckets"]) for p in pools)
    lat = np.asarray(lat_us)
    # None (JSON-safe), not NaN/zeros, when nothing was measured: a pool
    # of 1 has no warm calls, zero queries have no percentiles
    warm = float(np.median(dec_s[1:])) if dec_s[1:] else None
    stats = {"graphs": n_graphs, "queries": queries,
             "configs": [f"{m}-r{rr}s{sv}" for rr, sv, m in configs],
             "decompose_cold_s": dec_s[0],
             "decompose_warm_s": warm,
             "p50_us": float(np.percentile(lat, 50)) if queries else None,
             "p95_us": float(np.percentile(lat, 95)) if queries else None,
             "pools": [{"config": p["config"], "stats": p["stats"],
                        "hit_rate": p["hit_rate"]} for p in pools],
             "warm_hits": warm_hits,
             "n_buckets": n_buckets}
    if not quiet:
        warm_txt = "no warm calls (pool of 1)" if warm is None else (
            f"warm median {warm * 1e3:.0f}ms "
            f"({dec_s[0] / max(warm, 1e-9):.1f}x)")
        q_txt = "0 queries" if not queries else (
            f"{queries} queries p50={stats['p50_us']:.0f}us "
            f"p95={stats['p95_us']:.0f}us")
        print(f"warm pool: {n_graphs} graphs through {len(pools)} "
              f"router pool(s) ({n_buckets} shape bucket(s), "
              f"{warm_hits} warm hits): "
              f"cold {dec_s[0] * 1e3:.0f}ms, {warm_txt}; {q_txt}")
    return stats


def serve_nucleus(path: str = "", n_queries: int = 64, batch: int = 8,
                  seed: int = 0, quiet: bool = False):
    """Nucleus-query serving: decompose once (offline), query many (here).

    Loads a serialized ``Decomposition`` and answers ``n_queries`` queries
    in fixed-size batches — alternating ``cut(c)`` (nucleus labels) and
    ``nuclei(c)`` (vertex sets + densities) over random cut levels c.  The
    first query per level pays lazy tree/cut materialization; repeats hit
    the cache, which is exactly the decompose-once/query-many claim.
    Returns a stats dict (also printed unless quiet).
    """
    from ..core.api import Decomposition, NucleusConfig, decompose

    if path:
        dec = Decomposition.load(path)
    else:
        # no artifact supplied: build the offline stage inline on a small
        # planted graph, round-trip through JSON, and serve the reload —
        # the same code path a real offline artifact takes
        from ..graph import generators
        g = generators.planted_cliques(120, [10, 8, 6], 0.03, seed=3)
        offline = decompose(g, NucleusConfig(r=2, s=3, backend="dense",
                                             hierarchy="fused"))
        dec = Decomposition.from_json(offline.to_json())
    kmax = int(dec.core.max()) if dec.n_r else 0
    rng = np.random.default_rng(seed)
    lat_us: List[float] = []
    n_cut = n_nuc = 0
    t_all = time.perf_counter()
    for start in range(0, n_queries, batch):
        cs = rng.integers(1, max(kmax, 1) + 1, size=min(batch,
                                                        n_queries - start))
        for qi, c in enumerate(cs):
            t0 = time.perf_counter()
            if (start + qi) % 2 == 0:
                dec.cut(int(c))
                n_cut += 1
            else:
                dec.nuclei(int(c))
                n_nuc += 1
            lat_us.append((time.perf_counter() - t0) * 1e6)
    dt = time.perf_counter() - t_all
    lat = np.asarray(lat_us)
    # None (JSON-safe), not fake zeros, when no queries were served
    served = len(lat_us)
    stats = {"queries": served, "cut": n_cut, "nuclei": n_nuc,
             "qps": served / max(dt, 1e-9),
             "p50_us": float(np.percentile(lat, 50)) if served else None,
             "p95_us": float(np.percentile(lat, 95)) if served else None,
             "max_us": float(lat.max()) if served else None,
             "n_r": dec.n_r, "kmax": kmax}
    if not quiet:
        q_txt = "0 queries" if not served else (
            f"{stats['qps']:.0f} q/s, p50={stats['p50_us']:.0f}us "
            f"p95={stats['p95_us']:.0f}us max={stats['max_us']:.0f}us")
        print(f"served {served} nucleus queries "
              f"({n_cut} cut, {n_nuc} nuclei) from a serialized "
              f"decomposition (n_r={dec.n_r}, kmax={kmax}): {q_txt}")
    return stats


def _selftest_workload(host: str, port: int, quiet: bool = False
                       ) -> Dict[str, int]:
    """Drive the mixed CI-smoke workload over real HTTP and assert on it.

    Two same-bucket decomposes (the second MUST be a warm hit), a
    different-config decompose (second pool), cut + nuclei queries, one
    update delta (live version bump), and a status fetch validated
    against the schema.  Raises ``SystemExit`` on any violated
    invariant so the CI job fails loudly."""
    import urllib.request

    from ..graph import generators
    from ..serve import STATUS_FORMAT, validate_status

    def call(route: str, payload: Optional[Dict] = None) -> Dict:
        url = f"http://{host}:{port}{route}"
        if payload is None:
            req = urllib.request.Request(url)
        else:
            req = urllib.request.Request(
                url, data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            return json.loads(resp.read())

    def edges_of(g) -> List[List[int]]:
        return np.asarray(g.edges).tolist()

    def check(cond: bool, what: str) -> None:
        if not cond:
            raise SystemExit(f"server selftest failed: {what}")

    # sizes drift but stay inside one power-of-two shape class (the
    # warm-pool convention), so the second decompose MUST hit warm
    g0 = generators.planted_cliques(120, [10, 8, 6], 0.03, seed=3)
    g1 = generators.planted_cliques(122, [10, 8, 6], 0.03, seed=4)
    # same shape bucket + config as g0/g1 -> the warm path; distinct
    # artifact names so all three stay queryable
    a0 = call("/decompose", {"n": g0.n, "edges": edges_of(g0),
                             "r": 2, "s": 3, "artifact": "alpha"})
    a1 = call("/decompose", {"n": g1.n, "edges": edges_of(g1),
                             "r": 2, "s": 3, "artifact": "beta"})
    # a second tenant config -> a second router pool
    a2 = call("/decompose", {"n": g0.n, "edges": edges_of(g0),
                             "r": 1, "s": 2, "artifact": "gamma"})
    for name, art in (("alpha", a0), ("beta", a1), ("gamma", a2)):
        check(art["artifact"] == name and art["version"] == 0,
              f"decompose reply for {name!r}: {art}")
        check(art["plan"] and "backend" in art["plan"],
              f"decompose reply for {name!r} lacks an embedded plan")
    cut = call("/query", {"artifact": "alpha", "kind": "cut", "c": 1})
    check(len(cut["cut"]) == a0["n_r"], "cut length != n_r")
    nuc = call("/query", {"artifact": "beta", "kind": "nuclei", "c": 1})
    check(len(nuc["nuclei"]) >= 1, "no nuclei at c=1")
    upd = call("/update", {"artifact": "alpha",
                           "insert": [[0, int(g0.n - 1)]]})
    check(upd["version"] == 1, f"update did not bump version: {upd}")
    status = validate_status(call("/status"))
    check(status["format"] == STATUS_FORMAT, "bad status format")
    warm = sum(p["stats"]["warm"] for p in status["pools"])
    check(warm >= 1, f"expected >=1 warm hit after same-bucket pair, "
                     f"got {warm}")
    check(len(status["pools"]) == 2,
          f"expected 2 pools (two tenant configs), "
          f"got {len(status['pools'])}")
    check(status["artifacts"]["alpha"]["version"] == 1,
          "status does not show the updated live version")
    check(status["frontend"]["served"] >= 4, "served counter too low")
    out = {"decomposes": 3, "queries": 2, "updates": 1,
           "warm_hits": warm, "pools": len(status["pools"])}
    if not quiet:
        print(f"selftest ok: {out}")
    return out


def serve_nucleus_server(port: int = 0, cache_dir: str = "",
                         selftest: bool = False, max_queue: int = 64,
                         quiet: bool = False):
    """The real multi-tenant server (DESIGN.md §11).

    Builds the Router -> Frontend -> HTTP stack; with ``--cache-dir``, if
    a session manifest from a previous run exists there, it pre-warms the
    pools so the first same-bucket decompose after restart is a hit in
    the persistent compilation cache (``main`` turns that cache on,
    ``serve.cache.init_persistent_cache``).  On shutdown the manifest is
    (re)saved.  ``--selftest`` drives the mixed
    CI-smoke workload over HTTP and exits; without it the server blocks
    until SIGINT.
    """
    from ..serve import (Frontend, NucleusHTTPServer, Router, load_manifest,
                         prewarm_router, save_manifest)

    router = Router()
    prewarmed = 0
    if cache_dir:
        os.makedirs(cache_dir, exist_ok=True)
        manifest = load_manifest(cache_dir)
        if manifest is not None:
            prewarmed = prewarm_router(router, manifest)
    frontend = Frontend(router, max_queue=max_queue)
    server = NucleusHTTPServer(frontend, port=port)
    host, bound = server.start()
    if not quiet:
        print(f"nucleus server on http://{host}:{bound} "
              f"({prewarmed} bucket(s) pre-warmed"
              f"{' from ' + cache_dir if cache_dir else ''})")
    try:
        if selftest:
            out = _selftest_workload(host, bound, quiet=quiet)
            out["prewarmed"] = prewarmed
            return out
        while True:  # pragma: no cover - interactive serving loop
            time.sleep(1.0)
    except KeyboardInterrupt:  # pragma: no cover
        pass
    finally:
        server.stop()
        if cache_dir:
            save_manifest(router, cache_dir)
            if not quiet:
                print(f"session manifest saved to {cache_dir}")


def main() -> None:
    from .platform import setup_platform

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--platform", default="",
                    help="jax platform override (cpu|gpu|tpu); applied "
                         "before any device use, GPU adds the serving "
                         "XLA flag set")
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="host platform device count (0 = leave alone)")
    ap.add_argument("--decomposition", default="",
                    help="path to a serialized Decomposition JSON "
                         "(--arch nucleus); omitted = inline offline stage")
    ap.add_argument("--queries", type=int, default=64,
                    help="number of nucleus queries (--arch nucleus); "
                         "0 is honored (no query stage, percentiles None)")
    ap.add_argument("--warm-pool", action="store_true",
                    help="--arch nucleus: decompose a stream of graphs "
                         "through the plan-aware router (per-config "
                         "Session pools) instead of serving one artifact")
    ap.add_argument("--pool-graphs", type=int, default=5,
                    help="graphs in the warm pool (--warm-pool)")
    ap.add_argument("--bucket-cap", type=int, default=0,
                    help="LRU cap on each Session's tracked shape buckets "
                         "(--warm-pool); 0 = the Session default")
    ap.add_argument("--r", default="2",
                    help="nucleus r; comma list for mixed tenant configs "
                         "(--warm-pool)")
    ap.add_argument("--s", default="3",
                    help="nucleus s; comma list for mixed tenant configs "
                         "(--warm-pool)")
    ap.add_argument("--method", default="exact",
                    help="exact|approx; comma list for mixed tenant "
                         "configs (--warm-pool)")
    ap.add_argument("--server", action="store_true",
                    help="--arch nucleus: start the multi-tenant HTTP "
                         "server (Frontend + admission control)")
    ap.add_argument("--port", type=int, default=0,
                    help="--server port (0 = ephemeral)")
    ap.add_argument("--cache-dir", default="",
                    help="--server: session manifest directory (restart "
                         "warm path); the compilation cache is placed by "
                         "JAX_COMPILATION_CACHE_DIR, else <checkout>/"
                         ".jax_cache")
    ap.add_argument("--selftest", action="store_true",
                    help="--server: drive the mixed smoke workload over "
                         "HTTP, assert the status schema, and exit")
    args = ap.parse_args()
    setup_platform(platform=args.platform or None,
                   cpu_devices=args.cpu_devices or None)
    from ..serve.cache import init_persistent_cache
    init_persistent_cache()
    if args.arch == "nucleus":
        if args.server:
            serve_nucleus_server(port=args.port, cache_dir=args.cache_dir,
                                 selftest=args.selftest)
        elif args.warm_pool:
            serve_nucleus_warm_pool(n_graphs=args.pool_graphs,
                                    n_queries=args.queries // max(
                                        args.pool_graphs, 1),
                                    bucket_cap=args.bucket_cap,
                                    r=args.r, s=args.s, method=args.method)
        else:
            serve_nucleus(path=args.decomposition, n_queries=args.queries)
    elif args.arch == "din":
        serve_din(n_batches=4)
    else:
        serve_lm(args.arch, n_requests=args.requests)


if __name__ == "__main__":
    main()
