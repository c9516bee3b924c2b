"""platformProfile: tuned device profiles as data files, one loader
for every subsystem's knobs (ROADMAP item 1's unlocking refactor).

Knob resolution used to be scattered across per-subsystem
``resolve_*`` functions, each hand-rolling the same precedence ladder.
They now all declare their knobs as :class:`Knob` specs and resolve
through :func:`resolve_section`, which inserts ONE new layer — the
platform profile — into the ladder:

    explicit (config directive / kwarg)
      > CTMR_* env var
        > platform profile (this module)
          > built-in default

A profile is a JSON file (the ``platformProfile`` directive or the
``CTMR_PLATFORM_PROFILE`` env var):

.. code-block:: json

    {"version": 1,
     "platform": "tpu-v5e-8",
     "knobs": {"serve":   {"serveReplicas": 4},
               "verify":  {"verifyPrecompWindow": 16},
               "fleet":   {"numWorkers": 4},
               "filter":  {"filterFpRate": 0.005},
               "distrib": {"maxDeltaChain": 8}}}

so a deployment can hand in a versioned data file and every subsystem
picks its knobs up with zero code changes. Knob names inside a
section are the directive spellings (``serveReplicas``, not
``serve_replicas``). Unknown sections/knobs are ignored (forward
compatibility); an unreadable profile warns once and resolves as if
absent (the config layer's unparseable-value tolerance).

Two optional top-level blocks:

- ``"fingerprint"``: the platform identity the profile was measured
  on (:func:`current_fingerprint` — jax backend, device kind, device
  count, host cores). When present, it is compared against this
  host's fingerprint on the keys BOTH sides carry; a mismatch warns
  once and the profile resolves as if absent — a v5e-tuned profile
  must never silently steer a CPU box (or vice versa). Profiles
  without the block load as before.
- ``"provenance"``: per-knob measurement evidence (curves, reps,
  wall seconds). The loader tolerates and ignores it — provenance is
  for humans, never for resolution.

The config-parity lint rule covers this layer: every ``CTMR_*`` env
named in a :class:`Knob` spec must be documented in MIGRATING.md, and
every section name resolved here must appear in MIGRATING.md's
platformProfile documentation.
"""

from __future__ import annotations

import json
import os
import sys
from dataclasses import dataclass
from typing import Any, Callable, Optional

PROFILE_VERSION = 1

# Active profile state: explicit path (set_active_profile, from the
# platformProfile directive) beats the CTMR_PLATFORM_PROFILE env.
# Loaded profiles cache by path; a failed load caches the failure so
# the warning prints once per path, not per knob resolution.
_active_path: Optional[str] = None
_cache: dict[str, Optional[dict]] = {}


def set_active_profile(path: Optional[str]) -> None:
    """Pin the active profile path (ct-fetch calls this with the
    ``platformProfile`` directive before building any subsystem).
    ``None``/empty falls back to the CTMR_PLATFORM_PROFILE env."""
    global _active_path
    _active_path = path or None


def active_profile_path() -> str:
    return _active_path or os.environ.get("CTMR_PLATFORM_PROFILE", "")


def current_fingerprint() -> dict:
    """This host's platform identity, the key a tuned profile is
    matched against: jax backend + first-device kind + device count +
    host cores. jax imports lazily (and only when a profile actually
    carries a fingerprint block) so profile resolution never forces
    device acquisition; with jax unavailable the fingerprint degrades
    to the host-only keys and matching proceeds on those."""
    fp: dict = {"host_cores": os.cpu_count() or 1}
    try:
        import jax

        devs = jax.devices()
        fp["jax_backend"] = jax.default_backend()
        fp["device_kind"] = devs[0].device_kind if devs else ""
        fp["device_count"] = len(devs)
    except Exception:  # pragma: no cover - jax import/init failure
        pass
    return fp


def fingerprint_matches(profile_fp: dict,
                        current_fp: Optional[dict] = None) -> bool:
    """True when the profile's fingerprint agrees with this host on
    every key BOTH sides carry (a partial fingerprint — e.g. a profile
    that only pins ``device_kind`` — matches any host with that
    device). An empty/absent fingerprint matches everything: round-18
    profiles predate the block."""
    if not isinstance(profile_fp, dict) or not profile_fp:
        return True
    cur = current_fingerprint() if current_fp is None else current_fp
    return all(profile_fp[k] == cur[k] for k in profile_fp
               if k in cur)


def load_profile(path: str) -> Optional[dict]:
    """Parse + validate one profile file; None (with a one-time
    warning) when unreadable — a bad profile must never kill a run,
    matching the config layer's tolerance for unparseable values.
    A profile carrying a ``fingerprint`` block that does not match
    this host is rejected the same way (warn once, resolve as if
    absent); a ``provenance`` block is validated for shape and then
    ignored by resolution."""
    cached = _cache.get(path, False)
    if cached is not False:
        return cached
    prof: Optional[dict] = None
    try:
        with open(path) as fh:
            data = json.load(fh)
        if not isinstance(data, dict) or not isinstance(
                data.get("knobs", {}), dict):
            raise ValueError("profile must be a JSON object with a "
                             "'knobs' object")
        if data.get("version", PROFILE_VERSION) != PROFILE_VERSION:
            raise ValueError(f"unsupported profile version "
                             f"{data.get('version')!r}")
        fp = data.get("fingerprint")
        if fp is not None and not isinstance(fp, dict):
            raise ValueError("'fingerprint' must be a JSON object")
        if fp and not fingerprint_matches(fp):
            raise ValueError(
                f"platform fingerprint mismatch: profile measured on "
                f"{fp!r}, this host is {current_fingerprint()!r}")
        prov = data.get("provenance")
        if prov is not None and not isinstance(prov, dict):
            raise ValueError("'provenance' must be a JSON object")
        prof = data
    except (OSError, ValueError) as err:
        print(f"platformProfile ignored ({path}): {err}",
              file=sys.stderr)
    _cache[path] = prof
    return prof


def invalidate_cache(path: Optional[str] = None) -> None:
    """Drop the load cache for one path (or all): tests rewrite
    profile bytes at a reused path."""
    if path is None:
        _cache.clear()
    else:
        _cache.pop(path, None)


def profile_value(section: str, name: str) -> Any:
    """The active profile's value for one knob, or None."""
    path = active_profile_path()
    if not path:
        return None
    prof = load_profile(path)
    if not prof:
        return None
    knobs = prof.get("knobs", {})
    sec = knobs.get(section)
    if not isinstance(sec, dict):
        return None
    return sec.get(name)


# -- the knob engine ------------------------------------------------------


def _default_is_set(v: Any) -> bool:
    return v is not None


@dataclass(frozen=True)
class Knob:
    """One tunable: its directive-spelled name, env var, default, and
    the per-layer semantics that differ knob to knob (when is an
    explicit value "set"? how does the env string parse?)."""

    name: str
    env: str = ""
    default: Any = None
    # env string -> typed value; raising means "unparseable, ignored".
    parse: Callable[[str], Any] = int
    # Explicit/profile values count only when is_set says so (e.g. 0 =
    # unset for positive-int knobs, -1 = unset for sentinel ints).
    is_set: Callable[[Any], bool] = _default_is_set
    # Parsed env values get their own test when the env layer's unset
    # convention differs (None = same as is_set).
    env_is_set: Optional[Callable[[Any], bool]] = None
    # Final clamp/normalization applied to whichever layer won.
    post: Optional[Callable[[Any], Any]] = None


def _resolve_knob(section: str, knob: Knob, explicit: dict) -> Any:
    """One knob through the four-layer ladder (explicit, env, profile,
    default): the pre-post value."""
    ev = explicit.get(knob.name)
    if ev is not None and knob.is_set(ev):
        return ev
    if knob.env:
        raw = os.environ.get(knob.env, "")
        if raw:
            try:
                parsed = knob.parse(raw)
            except (TypeError, ValueError):
                parsed = None
            test = knob.env_is_set or knob.is_set
            if parsed is not None and test(parsed):
                return parsed
    pv = profile_value(section, knob.name)
    if pv is not None and knob.is_set(pv):
        return pv
    return knob.default


def resolve_section(section: str, knobs: tuple,
                    explicit: dict) -> dict:
    """Run the four-layer ladder for every knob of one section.
    ``explicit`` maps knob names to directive/kwarg values (typed, not
    strings)."""
    out = {}
    for knob in knobs:
        value = _resolve_knob(section, knob, explicit)
        if knob.post is not None:
            value = knob.post(value)
        out[knob.name] = value
    return out


# -- shared predicates/parsers (the recurring knob shapes) ---------------


def pos_int(v: Any) -> bool:
    """Positive-int knobs: 0 (and below) means "unset"."""
    try:
        return int(v) > 0
    except (TypeError, ValueError):
        return False


def nonneg_int(v: Any) -> bool:
    """Sentinel-int knobs: -1 means "unset", 0 is a real value."""
    try:
        return int(v) >= 0
    except (TypeError, ValueError):
        return False


def nonzero_int(v: Any) -> bool:
    """Knobs where negative values are meaningful (e.g. -1 disables a
    cache): only exactly 0 means "unset"."""
    try:
        return int(v) != 0
    except (TypeError, ValueError):
        return False


def nonempty_str(v: Any) -> bool:
    return isinstance(v, str) and bool(v)


def pos_float(v: Any) -> bool:
    try:
        return float(v) > 0
    except (TypeError, ValueError):
        return False


def parse_bool_lenient(raw: str) -> bool:
    """The serve-plane convention: anything but an explicit false
    spelling is true."""
    return raw.strip().lower() not in ("0", "f", "false")


def parse_bool_strict(raw: str) -> bool:
    """The emit-style convention: only explicit true spellings are
    true."""
    return raw.strip().lower() in ("1", "t", "true")


def any_set(_v: Any) -> bool:
    """env_is_set for bool knobs: a present, parseable env var always
    decides (False included)."""
    return True
