"""Profiler-driven autotuning: persisted per-backend ``TunedProfile``.

The engine's throughput constants — bucket set (``cmdqueue.BUCKETS``),
overlapped-drain toggle, staging-ring capacity, and the sharded jit-cache
bound (``fused_dispatch.MAX_DELTA_SIGNATURES``) — were hand-picked.
``benchmarks/bench_autotune.py`` sweeps them MEF-style (a parameterized
experiment matrix per machine/backend) against representative command
streams, measures ``us_per_flush``/launches with the shared obs timer,
picks winners via :func:`pick_winner`, and persists the result as a JSON
:class:`TunedProfile` under ``configs/tuned/<backend>.json``.

``RowCloneEngine`` / ``ServingEngine`` call :func:`load_profile` at
startup; precedence is **explicit kwarg > tuned profile > built-in
default**.  A missing profile file (or ``REPRO_NO_TUNED=1``) means
today's defaults, exactly as before.  :func:`pick_winner` keeps the
default configuration unless a candidate beats it by a clear margin
(default 3%), so a committed profile can never encode a noise-level
"win" that regresses other workloads.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
from typing import Dict, List, Optional, Sequence, Tuple

#: profile JSON schema version (bump on incompatible field changes)
PROFILE_SCHEMA = 1

#: required margin (fractional) before a candidate unseats the default
DEFAULT_MARGIN = 0.03

_LOGGED: set = set()


@dataclasses.dataclass(frozen=True)
class TunedProfile:
    """One backend's tuned engine constants + the measurements behind
    them.  ``ring_capacity=None`` keeps the serving layer's
    policy-derived staging ring; every field falls back to the built-in
    default when an engine kwarg overrides it."""

    backend: str                              #: jax backend key ("cpu", "tpu")
    buckets: Tuple[int, ...] = (8, 32, 128, 512)   #: table bucket sizes
    overlap: bool = True                      #: overlapped DMA drain
    max_delta_signatures: int = 8             #: sharded jit-cache fold bound
    ring_capacity: Optional[int] = None       #: staging ring slots (None = policy)
    us_per_flush: float = 0.0                 #: winner's measured median
    baseline_us_per_flush: float = 0.0        #: defaults' measured median
    swept: Dict = dataclasses.field(default_factory=dict)  #: sweep summary
    schema: int = PROFILE_SCHEMA              #: profile format version

    def to_dict(self) -> Dict:
        """JSON-ready dict (tuples become lists)."""
        d = dataclasses.asdict(self)
        d["buckets"] = list(self.buckets)
        return d

    @classmethod
    def from_dict(cls, d: Dict) -> "TunedProfile":
        """Rebuild from :meth:`to_dict` output (unknown keys ignored so
        newer files load under older code)."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        kw["buckets"] = tuple(int(b) for b in kw.get("buckets",
                                                     (8, 32, 128, 512)))
        if kw.get("ring_capacity") is not None:
            kw["ring_capacity"] = int(kw["ring_capacity"])
        return cls(**kw)


def tuned_dir() -> pathlib.Path:
    """Directory holding per-backend profile JSONs: ``$REPRO_TUNED_DIR``
    when set, else ``configs/tuned/`` at the repo root."""
    env = os.environ.get("REPRO_TUNED_DIR")
    if env:
        return pathlib.Path(env)
    return pathlib.Path(__file__).resolve().parents[3] / "configs" / "tuned"


def backend_key() -> str:
    """The profile key for this process: ``jax.default_backend()``
    ("cpu", "tpu", "gpu").  A backend that cannot be queried raises:
    pretending to be the CPU would load the CPU's tuned constants on
    another device."""
    import jax
    return str(jax.default_backend())


def profile_path(backend: Optional[str] = None,
                 directory: Optional[pathlib.Path] = None) -> pathlib.Path:
    """Path of ``backend``'s profile file (default: this process's
    backend under :func:`tuned_dir`)."""
    backend = backend or backend_key()
    directory = pathlib.Path(directory) if directory else tuned_dir()
    return directory / f"{backend}.json"


def save_profile(profile: TunedProfile,
                 directory: Optional[pathlib.Path] = None) -> pathlib.Path:
    """Persist ``profile`` as ``<dir>/<backend>.json`` (dir created);
    returns the written path."""
    path = profile_path(profile.backend, directory)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(profile.to_dict(), indent=2,
                               sort_keys=True) + "\n")
    return path


def load_profile(backend: Optional[str] = None,
                 directory: Optional[pathlib.Path] = None
                 ) -> Optional[TunedProfile]:
    """Load the backend's :class:`TunedProfile`, or None when no file
    exists (or ``REPRO_NO_TUNED=1`` opts out).  Logs one startup line
    per (backend, path) the first time a profile loads in a process —
    the "engine demonstrably loaded it" breadcrumb."""
    if os.environ.get("REPRO_NO_TUNED"):
        return None
    path = profile_path(backend, directory)
    if not path.is_file():
        return None
    try:
        prof = TunedProfile.from_dict(json.loads(path.read_text()))
    except (ValueError, TypeError, KeyError):
        return None       # malformed file degrades to defaults
    tag = (prof.backend, str(path))
    if tag not in _LOGGED:
        _LOGGED.add(tag)
        print(f"[obs] tuned profile loaded: backend={prof.backend} "
              f"buckets={list(prof.buckets)} overlap={prof.overlap} "
              f"max_delta_signatures={prof.max_delta_signatures} "
              f"ring_capacity={prof.ring_capacity} ({path})")
    return prof


def apply_profile(profile: TunedProfile) -> Dict[str, object]:
    """Install the profile's PROCESS-WIDE knobs: the cmdqueue bucket set
    and the sharded-dispatch delta-signature bound.  (Per-engine knobs —
    ``overlap``, ``ring_capacity`` — resolve inside engine ``__init__``
    where explicit kwargs can win.)  Returns the applied values."""
    from repro.core import cmdqueue
    from repro.kernels import fused_dispatch
    cmdqueue.set_buckets(profile.buckets)
    fused_dispatch.set_max_delta_signatures(profile.max_delta_signatures)
    return {"buckets": tuple(profile.buckets),
            "max_delta_signatures": profile.max_delta_signatures}


def pick_winner(rows: Sequence[Dict], default_cfg: Dict,
                margin: float = DEFAULT_MARGIN) -> Dict:
    """Choose the sweep's winning configuration.

    ``rows`` are sweep results ``{"cfg": {...}, "us_per_flush": float}``;
    ``default_cfg`` names the hand-picked configuration's cfg dict.  The
    fastest candidate wins ONLY if it beats the default's measured
    ``us_per_flush`` by more than ``margin`` (fractional) — otherwise
    the default is kept, so noise can never flip a committed constant.
    Returns the winning row (the default's row when it holds)."""
    if not rows:
        raise ValueError("pick_winner needs at least one sweep row")
    default_rows = [r for r in rows if r["cfg"] == default_cfg]
    if not default_rows:
        raise ValueError("sweep must include the default configuration")
    default_row = min(default_rows, key=lambda r: r["us_per_flush"])
    best = min(rows, key=lambda r: r["us_per_flush"])
    if best["us_per_flush"] < default_row["us_per_flush"] * (1.0 - margin):
        return best
    return default_row


__all__ = [
    "TunedProfile",
    "PROFILE_SCHEMA",
    "DEFAULT_MARGIN",
    "tuned_dir",
    "backend_key",
    "profile_path",
    "save_profile",
    "load_profile",
    "apply_profile",
    "pick_winner",
]
