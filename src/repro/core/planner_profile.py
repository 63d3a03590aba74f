"""Telemetry-driven planner thresholds: the measured profile + loader.

``resolve_plan``'s decision constants (``TINY_NR``, the compile-vs-eager
crossover; ``SHARD_MIN_INCIDENCE``, the shard-vs-single-device crossover)
and the ``use_pallas=None`` default were hand-set in PR 1–5.  This module
replaces them with *measured* crossovers, per device kind:

  * ``tools/calibrate_planner.py`` times the real engines on a problem-size
    ladder and writes ``planner_profile.json`` next to this file (or any
    path via ``--out``).  The committed file is a CPU profile measured on
    the reference container — the shipped default.
  * ``resolve_plan`` and ``engine.pallas_by_default()`` read the profile
    through the loaders here; every consumer records which profile entry
    fired (or that it fell back) so ``plan_report()`` says why a lane was
    chosen.
  * The static constants remain the documented, tested fallback: a missing
    file, malformed JSON, or an uncovered device kind degrades to exactly
    the PR-5 behaviour — with a warning the first time, not silently.

Lookup is by device kind first (``jax.devices()[0].device_kind``, e.g.
"TPU v4"), then platform (``jax.default_backend()``, e.g. "cpu"): a
calibration run records both keys, so a profile measured on one TPU
generation does not silently govern another.

Import-light on purpose (json/os only — no jax): ``backends`` imports this
at module load.
"""
from __future__ import annotations

import json
import os
import warnings
from typing import Any, Dict, Optional, Tuple

FORMAT = "repro.planner-profile"
VERSION = 1
PROFILE_PATH = os.path.join(os.path.dirname(__file__),
                            "planner_profile.json")

# The PR-5 hand-set constants — the verified fallback when no profile
# entry covers the device (backends.py re-exports them under their
# historical names).
STATIC_TINY_NR = 64
STATIC_SHARD_MIN_INCIDENCE = 1 << 20

_CACHE: Dict[str, Optional[Dict[str, Any]]] = {}
_WARNED: set = set()


def _warn_once(key: str, message: str) -> None:
    if key not in _WARNED:
        _WARNED.add(key)
        warnings.warn(message, stacklevel=3)


def load_profile(path: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """The parsed profile dict, or None (missing/malformed file — each
    malformed file warns once and then degrades to the static constants).
    Cached per path; ``reset_cache()`` drops the cache (tests)."""
    path = path or PROFILE_PATH
    if path in _CACHE:
        return _CACHE[path]
    prof: Optional[Dict[str, Any]] = None
    if os.path.exists(path):
        try:
            with open(path) as f:
                blob = json.load(f)
            if blob.get("format") != FORMAT or "profiles" not in blob:
                raise ValueError(
                    f"expected format={FORMAT!r} with a 'profiles' map, "
                    f"got keys {sorted(blob)}")
            prof = blob
        except (ValueError, OSError) as e:
            _warn_once(f"malformed:{path}",
                       f"planner profile {path} is unreadable ({e}); "
                       f"falling back to the static planner constants")
    return _CACHE.setdefault(path, prof)


def reset_cache() -> None:
    """Drop the load cache and warn-once state (test isolation)."""
    _CACHE.clear()
    _WARNED.clear()


def profile_entry(device_kind: Optional[str] = None,
                  platform: Optional[str] = None,
                  path: Optional[str] = None
                  ) -> Tuple[Optional[Dict[str, Any]], str]:
    """(entry, source_tag) for this device: the most specific profile
    entry (device kind beats platform), or (None, "static defaults")."""
    prof = load_profile(path)
    if prof is not None:
        profiles = prof["profiles"]
        for key in (device_kind, platform):
            if key and key in profiles:
                return profiles[key], f"planner_profile[{key!r}]"
    return None, "static defaults"


def thresholds(device_kind: Optional[str] = None,
               platform: Optional[str] = None,
               path: Optional[str] = None) -> Dict[str, Any]:
    """The planner's decision thresholds for this device + provenance.

    Returns {"tiny_nr", "shard_min_incidence", "source"}; each threshold
    falls back to its static constant individually (a profile entry may
    have measured only one crossover)."""
    entry, source = profile_entry(device_kind, platform, path)
    entry = entry or {}
    return {
        "tiny_nr": int(entry.get("tiny_nr", STATIC_TINY_NR)),
        "shard_min_incidence": int(entry.get("shard_min_incidence",
                                             STATIC_SHARD_MIN_INCIDENCE)),
        "source": source,
    }


def pallas_default(platform: Optional[str] = None,
                   device_kind: Optional[str] = None,
                   path: Optional[str] = None) -> Optional[bool]:
    """The profile's measured ``use_pallas=None`` verdict, or None.

    None means no profile entry covers this device (or the entry never
    measured the kernel race): the caller falls back to its static oracle
    — and we warn once per platform, so a fleet running uncalibrated is
    visible without spamming every decompose call."""
    entry, _source = profile_entry(device_kind, platform, path)
    if entry is not None and entry.get("pallas_default") is not None:
        return bool(entry["pallas_default"])
    _warn_once(
        f"pallas_default:{device_kind}:{platform}",
        f"no planner profile entry covers device_kind={device_kind!r} / "
        f"platform={platform!r}; use_pallas=None falls back to the static "
        f"platform oracle (Pallas on TPU).  Run tools/calibrate_planner.py "
        f"(or `make calibrate`) to measure this device.")
    return None


def use_pallas_default(platform: Optional[str],
                       device_kind: Optional[str] = None,
                       path: Optional[str] = None) -> bool:
    """What ``use_pallas=None`` resolves to on this device: the profile's
    measured verdict when one covers it, else the static platform oracle
    (Pallas on TPU, XLA scatter-add elsewhere — interpret-mode Pallas is
    a correctness oracle, not a fast path)."""
    v = pallas_default(platform, device_kind, path)
    return v if v is not None else platform == "tpu"
