"""The persistent warm path: compile caching + session manifests.

A warm ``Session`` dies with its process: every restart pays the full
XLA compile for each shape bucket before the pool is warm again — the
dominant serving cost, multiplied by every deploy.  Two pieces make the
pool survive restarts:

  * **Persistent compilation cache.**  ``init_persistent_cache()`` turns
    on jax's on-disk executable cache (min-entry-size and
    min-compile-time gates opened so even small peel executables
    persist).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own
    setting places it; otherwise it lives at the fixed in-checkout path
    ``DEFAULT_CACHE_DIR``.  The cache key includes the path, so a path
    built from a temporary name, a process id or the time would never
    hit.  Compiles keyed on the same HLO — same padded shapes, same
    statics — are then disk loads in any later process.
  * **Session manifest.**  ``save_manifest``/``load_manifest`` persist
    ``Router.manifest()`` (one ``Session.manifest()`` per pool: the
    shape-class records, nothing graph-specific) as JSON.
    ``Router.prewarm(manifest)`` recreates each pool and runs every
    bucket's all-ghost twin through the engine, turning the disk cache
    into live jitted callables — the first post-restart same-bucket
    decompose is a warm hit, not a multi-second compile.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

from ..core.session import MANIFEST_FORMAT

ROUTER_MANIFEST_FORMAT = "repro.nucleus-server-manifest"
ROUTER_MANIFEST_VERSION = 1
MANIFEST_BASENAME = "session_manifest.json"


DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def init_persistent_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, stands as jax read it and no
    other directory is set; otherwise the cache goes to
    ``DEFAULT_CACHE_DIR`` (``<checkout>/.jax_cache``).  Must run before
    the executables of interest compile (at process start, right after
    ``launch.platform.setup_platform``).  The size and compile-time gates
    are opened fully: peel executables for small shape buckets compile
    fast enough that jax's stock thresholds would skip exactly the
    entries a restarted server needs.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def router_manifest(router) -> Dict[str, Any]:
    """One manifest per pool, wrapped in the server envelope (the
    restart contract: everything ``Router.prewarm`` needs, nothing
    graph- or tenant-specific)."""
    with router._lock:
        pools = list(router._pools.values())
    return {"format": ROUTER_MANIFEST_FORMAT,
            "version": ROUTER_MANIFEST_VERSION,
            "pools": [sess.manifest() for sess in pools]}


def prewarm_router(router, manifest: Dict[str, Any]) -> int:
    """Recreate every manifest pool on ``router`` and prewarm its shape
    buckets; returns the total bucket count prewarmed.  Pools that
    already exist prewarm in place (idempotent across repeated calls —
    already-registered buckets are skipped by ``Session.prewarm``)."""
    from ..core.api import NucleusConfig

    if manifest.get("format") != ROUTER_MANIFEST_FORMAT:
        raise ValueError(
            f"not a server manifest: format={manifest.get('format')!r} "
            f"(expected {ROUTER_MANIFEST_FORMAT!r}) — regenerate it with "
            f"serve.cache.router_manifest()")
    total = 0
    for pool_manifest in manifest.get("pools", []):
        if pool_manifest.get("format") != MANIFEST_FORMAT:
            raise ValueError(
                f"malformed pool entry: format="
                f"{pool_manifest.get('format')!r} — the manifest was "
                f"truncated or hand-edited; regenerate it")
        config = NucleusConfig.from_dict(pool_manifest["config"])
        sess = router.pool(config)
        total += sess.prewarm(pool_manifest)
    return total


def save_manifest(router, path: str) -> str:
    """Serialize ``router_manifest(router)`` to ``path`` (a directory
    gets ``session_manifest.json`` inside it).  Returns the file path."""
    if os.path.isdir(path):
        path = os.path.join(path, MANIFEST_BASENAME)
    blob = router_manifest(router)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(blob, f, sort_keys=True, indent=1)
        f.write("\n")
    os.replace(tmp, path)  # atomic: a crash never leaves a torn manifest
    return path


def load_manifest(path: str) -> Optional[Dict[str, Any]]:
    """Read a manifest written by ``save_manifest``; a directory resolves
    to ``session_manifest.json`` inside it.  Returns None when the file
    does not exist (a first boot), raises on a malformed one."""
    if os.path.isdir(path):
        path = os.path.join(path, MANIFEST_BASENAME)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        blob = json.load(f)
    if blob.get("format") != ROUTER_MANIFEST_FORMAT:
        raise ValueError(
            f"{path}: not a server manifest (format="
            f"{blob.get('format')!r}); delete it or regenerate with "
            f"save_manifest()")
    return blob
