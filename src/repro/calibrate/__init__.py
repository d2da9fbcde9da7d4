"""``repro.calibrate`` — measured per-host cost profiles for the strategy
auction.

The backend cost hooks (:func:`repro.compile.xla_level_cost`,
:func:`repro.compile.spmd.spmd_level_cost`) and the interpreters' default
depth × statement-groups model price strategy offers with hand-set
constants tuned on one developer box.  This package replaces those
constants with *measured* ones: at first use (:func:`warm`) it runs a
small suite of synthetic microbenchmarks through the real lowering
machinery (:mod:`repro.calibrate.microbench`) and persists the resulting
:class:`CostProfile` as a schema-versioned JSON file keyed by a host
fingerprint (platform / device count / jax version), so serving restarts
reuse it with zero re-measurement.

Design contract:

* **Nothing measures implicitly.**  The cost hooks read the active profile
  through :func:`units`, which never triggers a microbenchmark — with no
  profile warmed, they resolve the hand-set module constants *late*
  (``repro.compile.XLA_STEP_LANE_UNITS`` and friends), so monkeypatched
  values take effect everywhere and test runs stay deterministic.
* **Calibration never enters structural cache keys.**  Like the
  ``level_cost`` hook it feeds (see :func:`repro.core.policy.resolve_policy`),
  the profile re-prices offers but is invisible to
  ``structural_key`` — two processes with different profiles share
  artifacts; only the auction outcome may differ.
* **Corrupt / stale files fall back to defaults.**  A profile that fails
  schema, fingerprint, or unit validation is ignored
  (``calibrate.fallbacks`` counter) and the hand-set constants apply.
* ``REPRO_CALIBRATE=off`` (or ``0`` / ``false``) pins the hand-set
  defaults regardless of any warmed or persisted profile;
  ``REPRO_CALIBRATE_DIR`` overrides the cache directory.

Metrics (unified ``repro.obs.metrics`` registry): ``calibrate.measurements``
(one per timed microbenchmark sample — flat across a restart that reuses a
persisted profile), ``calibrate.loads``, ``calibrate.fallbacks`` counters
and the ``calibrate.generation`` gauge.  :func:`reset` (installed in
``obs.reset_all()``) restores the in-memory default state without touching
persisted files.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import platform as _platform
import sys
import tempfile
import threading
from pathlib import Path
from typing import Dict, Optional

from repro.obs import metrics as _metrics

__all__ = [
    "SCHEMA_VERSION",
    "UNIT_NAMES",
    "CostProfile",
    "active_profile",
    "cache_dir",
    "default_profile",
    "dispatch_units",
    "enabled",
    "host_fingerprint",
    "host_info",
    "load_profile",
    "measure",
    "profile_generation",
    "profile_path",
    "reset",
    "save_profile",
    "set_profile",
    "summary_pointer",
    "unit",
    "units",
    "warm",
]

SCHEMA_VERSION = 1

# The five calibrated unit costs.  All are relative weights inside one
# backend's auction, so hand-set defaults (abstract units) and measured
# values (microseconds) are both legitimate — they are never mixed within
# one profile.
#   xla_step             flat per-level cost of the jitted band step
#   xla_lane             per padded lane on top of it
#   spmd_collective      flat per-level collective cost on the mesh
#   spmd_collective_lane per gathered lane of that collective
#   dispatch             per batched group dispatch of the interpreters
UNIT_NAMES = (
    "xla_step",
    "xla_lane",
    "spmd_collective",
    "spmd_collective_lane",
    "dispatch",
)


@dataclasses.dataclass(frozen=True)
class CostProfile:
    """One host's measured (or default) cost units.

    ``source`` is ``"default"`` (hand-set constants, generation 0),
    ``"measured"`` (fresh microbenchmarks this process) or ``"persisted"``
    (reloaded from the cache dir with zero re-measurement).
    """

    units: Dict[str, float]
    fingerprint: str
    generation: int = 0
    source: str = "default"
    schema: int = SCHEMA_VERSION
    meta: Dict[str, object] = dataclasses.field(default_factory=dict)

    def as_dict(self) -> dict:
        return {
            "schema": self.schema,
            "fingerprint": self.fingerprint,
            "generation": self.generation,
            "units": {k: float(self.units[k]) for k in UNIT_NAMES},
            "meta": dict(self.meta),
        }


_LOCK = threading.Lock()
_ACTIVE: Optional[CostProfile] = None


# ---------------------------------------------------------------------- #
# Environment / host identity
# ---------------------------------------------------------------------- #

def enabled() -> bool:
    """False when ``REPRO_CALIBRATE`` is ``off``/``0``/``false`` — the
    hand-set defaults then apply regardless of warmed/persisted state."""

    return os.environ.get("REPRO_CALIBRATE", "").strip().lower() not in (
        "off",
        "0",
        "false",
    )


def host_info() -> Dict[str, str]:
    """The identity a profile is keyed by: platform, accelerator backend,
    device count and jax version."""

    import jax

    return {
        "machine": _platform.machine(),
        "system": _platform.system(),
        "backend": jax.default_backend(),
        "devices": str(jax.local_device_count()),
        "jax": str(jax.__version__),
    }


def host_fingerprint(info: Optional[Dict[str, str]] = None) -> str:
    info = info if info is not None else host_info()
    raw = "|".join(
        f"{k}={info[k]}"
        for k in ("machine", "system", "backend", "devices", "jax")
    )
    return hashlib.sha1(raw.encode()).hexdigest()[:16]


def cache_dir() -> Path:
    """Profile directory: ``REPRO_CALIBRATE_DIR`` when set, else the
    XDG-style user cache (``~/.cache/repro-calibrate``)."""

    override = os.environ.get("REPRO_CALIBRATE_DIR")
    if override:
        return Path(override)
    xdg = os.environ.get("XDG_CACHE_HOME")
    base = Path(xdg) if xdg else Path.home() / ".cache"
    return base / "repro-calibrate"


def profile_path(fingerprint: Optional[str] = None) -> Path:
    fp = fingerprint if fingerprint is not None else host_fingerprint()
    return cache_dir() / f"cost_profile-{fp}.json"


# ---------------------------------------------------------------------- #
# Default (hand-set) units, resolved LATE
# ---------------------------------------------------------------------- #

def _hand_set_units() -> Dict[str, float]:
    """Today's module constants, read at call time — monkeypatching
    ``repro.compile.XLA_STEP_LANE_UNITS`` (or the spmd/policy constants)
    changes every consumer, which is the satellite fix for the old
    import-by-value in ``spmd.py``."""

    import repro.compile as _compile

    spmd = sys.modules.get("repro.compile.spmd")
    policy = sys.modules.get("repro.core.policy")
    return {
        "xla_step": float(_compile.XLA_STEP_LANE_UNITS),
        "xla_lane": float(getattr(_compile, "XLA_LANE_UNITS", 1.0)),
        "spmd_collective": float(
            getattr(spmd, "SPMD_COLLECTIVE_UNITS", 4.0)
        ),
        "spmd_collective_lane": float(
            getattr(spmd, "SPMD_COLLECTIVE_LANE_UNITS", 0.125)
        ),
        "dispatch": float(getattr(policy, "DISPATCH_UNITS", 1.0)),
    }


def default_profile() -> CostProfile:
    return CostProfile(
        units=_hand_set_units(),
        fingerprint=host_fingerprint(),
        generation=0,
        source="default",
    )


# ---------------------------------------------------------------------- #
# Active-profile state
# ---------------------------------------------------------------------- #

def active_profile() -> CostProfile:
    """The installed profile, or a fresh default snapshot when none (or
    when calibration is disabled via the env switch)."""

    with _LOCK:
        prof = _ACTIVE
    if prof is None or not enabled():
        return default_profile()
    return prof


def set_profile(profile: Optional[CostProfile]) -> None:
    global _ACTIVE
    with _LOCK:
        _ACTIVE = profile
    _metrics.gauge("calibrate.generation").set(
        0 if profile is None else profile.generation
    )


def reset() -> None:
    """Back to hand-set defaults in-memory (``obs.reset_all()`` hook).
    Persisted profile files are left on disk — restarts reuse them."""

    global _ACTIVE
    with _LOCK:
        _ACTIVE = None


def units() -> Dict[str, float]:
    """The unit costs every cost hook prices with *right now*."""

    prof = active_profile()
    if prof.source == "default":
        # a default snapshot may be stale vs a just-monkeypatched constant;
        # re-resolve late
        return _hand_set_units()
    return dict(prof.units)


def unit(name: str) -> float:
    if name not in UNIT_NAMES:
        raise KeyError(
            f"unknown calibration unit {name!r}; expected one of {UNIT_NAMES}"
        )
    return units()[name]


def dispatch_units() -> float:
    """Per-group dispatch weight of the interpreters' default cost model."""

    return units()["dispatch"]


def profile_generation() -> int:
    """Generation of the profile pricing the auction (0 = hand-set)."""

    prof = active_profile()
    return prof.generation if prof.source != "default" else 0


def summary_pointer() -> dict:
    """Deterministic pointer for ``report.summary()["obs"]`` — state flags
    plus where the full profile lives, never measured values."""

    prof = active_profile()
    return {
        "enabled": enabled(),
        "source": prof.source,
        "generation": prof.generation,
        "profile_export": (
            "repro.calibrate.active_profile() / profile_path()"
        ),
    }


# ---------------------------------------------------------------------- #
# Persistence
# ---------------------------------------------------------------------- #

def _valid_units(raw: object) -> Optional[Dict[str, float]]:
    if not isinstance(raw, dict):
        return None
    out: Dict[str, float] = {}
    for name in UNIT_NAMES:
        v = raw.get(name)
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            return None
        v = float(v)
        if not (v > 0.0) or v != v or v == float("inf"):
            return None
        out[name] = v
    return out


def load_profile(path: Optional[Path] = None) -> Optional[CostProfile]:
    """Read + validate a persisted profile; ``None`` (and a
    ``calibrate.fallbacks`` tick) on a missing, corrupt, schema-mismatched
    or foreign-host file — the caller falls back to defaults or
    re-measures."""

    path = Path(path) if path is not None else profile_path()
    try:
        raw = json.loads(path.read_text())
    except FileNotFoundError:
        return None
    except (OSError, ValueError):
        _metrics.counter("calibrate.fallbacks").inc()
        return None
    units_d = _valid_units(raw.get("units")) if isinstance(raw, dict) else None
    if (
        units_d is None
        or raw.get("schema") != SCHEMA_VERSION
        or raw.get("fingerprint") != host_fingerprint()
        or isinstance(raw.get("generation"), bool)
        or not isinstance(raw.get("generation"), int)
        or raw["generation"] < 0
    ):
        _metrics.counter("calibrate.fallbacks").inc()
        return None
    meta = raw.get("meta")
    return CostProfile(
        units=units_d,
        fingerprint=raw["fingerprint"],
        generation=raw["generation"],
        source="persisted",
        meta=dict(meta) if isinstance(meta, dict) else {},
    )


def save_profile(
    profile: CostProfile, path: Optional[Path] = None
) -> Path:
    """Atomic write (tempfile in the target dir + ``os.replace``), so a
    concurrent reader never sees a partial profile."""

    path = Path(path) if path is not None else profile_path(
        profile.fingerprint
    )
    path.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(
        dir=str(path.parent), prefix=path.name, suffix=".tmp"
    )
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(profile.as_dict(), f, indent=2, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


# ---------------------------------------------------------------------- #
# Measurement entry points
# ---------------------------------------------------------------------- #

def measure(persist: bool = True, **bench_kwargs) -> CostProfile:
    """Run the microbenchmark suite and install (and by default persist)
    the resulting profile.  A no-op returning the defaults when the env
    switch disables calibration.  ``bench_kwargs`` forward to
    :func:`repro.calibrate.microbench.measure_units` (tests shrink the
    problem sizes through them)."""

    if not enabled():
        return default_profile()
    from repro.calibrate import microbench as _mb

    units_d, meta = _mb.measure_units(**bench_kwargs)
    prev = load_profile()
    info = host_info()
    meta = dict(meta)
    meta.update(info)
    prof = CostProfile(
        units=units_d,
        fingerprint=host_fingerprint(info),
        generation=(prev.generation if prev is not None else 0) + 1,
        source="measured",
        meta=meta,
    )
    if persist:
        save_profile(prof)
    set_profile(prof)
    return prof


def warm(**bench_kwargs) -> CostProfile:
    """The documented "first use": reuse an already-installed or persisted
    profile (zero re-measurement — ``calibrate.measurements`` stays flat),
    else measure and persist one.  ``PlanService`` calls this at startup
    when ``ServiceOptions(warm_profile=True)``."""

    if not enabled():
        return default_profile()
    with _LOCK:
        prof = _ACTIVE
    if prof is not None and prof.source != "default":
        return prof
    prof = load_profile()
    if prof is not None:
        set_profile(prof)
        _metrics.counter("calibrate.loads").inc()
        return prof
    return measure(**bench_kwargs)
