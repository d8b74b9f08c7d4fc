"""Persistent driver-artifact cache: tuned drivers survive the process.

The paper's pipeline is compile-time-expensive (probe -> SVD fit -> codegen)
and runtime-cheap; a serving fleet cannot re-pay the compile-time cost in
every worker process.  This module is the durable tuning-results store (the
MITuna find-db analogue): each built driver program is written to disk as a
JSON artifact, content-addressed by a *build key* -- the SHA-256 of the
kernel spec fingerprint, the hardware parameters, and the fit hyperparameters
-- so any change to the spec, the target device, or the tuning settings
invalidates the entry by construction.

Two hashes protect an entry:

  * ``key``          -- hash of the build inputs (lookup address).  A spec
                        or hyperparameter change produces a different key,
                        so stale artifacts are simply never found.
  * ``content_hash`` -- hash of the stored payload (driver source + fitted
                        coefficients).  Verified on every read; a mismatch
                        (corruption, manual edit, partial write) invalidates
                        the entry, which is deleted and treated as a miss.

Layout: ``<root>/<kernel>/<key>.json``.  The root defaults to
``$KLARAPTOR_CACHE_DIR`` or ``~/.cache/klaraptor``.

A second entry kind, ``PlanEntry`` (``<root>/<kernel>/<key>.plan.json``),
stores *compiled launch plans*: the precomputed (shape -> config) tables
produced by ``choose_many`` over a serving process's traffic envelope (see
core/plan.py).  Plan entries carry the same two-hash protection and the
same ``tuning_version`` generation ordering as driver entries, and
``invalidate(below_version=...)`` evicts both kinds together -- a drift
refit that retires a fit generation retires its plans with it.

``Klaraptor.build_driver`` writes through this store; the driver registry
(``core/driver.py``) reads through it, so ``choose_or_default`` -- and with
it ``kernels/ops.py`` and the serving engine -- warm-start tuned drivers
built by any earlier process.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Mapping

from .device_model import HardwareParams
from .kernel_spec import KernelSpec

__all__ = [
    "DriverCache", "CacheEntry", "PlanEntry", "cache_key",
    "spec_fingerprint", "default_cache", "default_cache_dir",
]

# v2: collect() derives per-batch probe rngs (order-independent shards for
# fleet tuning), which changes the collected dataset for an otherwise
# identical key -- old artifacts must never be found.
_ENTRY_VERSION = 2


def default_cache_dir() -> str:
    env = os.environ.get("KLARAPTOR_CACHE_DIR")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "klaraptor")


def _write_json_atomic(path: str, raw: Any) -> None:
    """Publish ``raw`` at ``path`` in one atomic step.

    The temp file name is unique per writer (mkstemp), so concurrent
    write-throughs of the same key -- many fleet workers finishing the
    same generation at once -- never interleave into one temp file; each
    ``os.replace`` publishes a complete document and the last writer wins
    (same-generation entries are interchangeable: the content hash covers
    everything that matters).
    """
    fd, tmp = tempfile.mkstemp(
        prefix=os.path.basename(path) + ".", suffix=".tmp",
        dir=os.path.dirname(path) or ".")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(raw, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


def _canonical(obj: Any) -> str:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


def _sha(payload: Any) -> str:
    return hashlib.sha256(_canonical(payload).encode()).hexdigest()


def spec_fingerprint(spec: KernelSpec) -> dict:
    """JSON-able description of everything about a spec that affects the
    built driver.  Any edit to the spec changes the fingerprint and hence
    the cache key (stale-by-construction)."""
    fp = {
        "name": spec.name,
        "data_params": list(spec.data_params),
        "program_params": list(spec.program_params),
        "grid": [[a.name, a.data, a.block] for a in spec.grid],
        "operands": [[op.name, list(op.tile), list(op.deps),
                      op.dtype_bytes, op.is_output, op.vmem_only]
                     for op in spec.operands],
        # A number, or the text of a P-dependent FLOP expression: a spec
        # that changes either never finds the other's drivers.
        "flops_per_point": spec.flops_per_point,
        "constraints": list(spec.constraints),
        "mxu_fraction": spec.mxu_fraction,
        "param_candidates": {k: list(v)
                             for k, v in sorted(spec.param_candidates.items())},
        "pipeline_buffers": spec.pipeline_buffers,
        "fit_vars": {k: list(v) for k, v in sorted(spec.fit_vars.items())},
        "probe_hints": {k: list(v)
                        for k, v in sorted(spec.probe_hints.items())},
    }
    # Introspected specs carry the content identity of the traced kernel:
    # editing the kernel body changes the fingerprint and hence the cache
    # key, so stale tuning artifacts are never found.  Folded in only when
    # set, so hand-written specs keep their existing keys.
    if getattr(spec, "source_fingerprint", ""):
        fp["source_fingerprint"] = spec.source_fingerprint
    return fp


def cache_key(spec: KernelSpec, hw: HardwareParams,
              hyper: Mapping[str, Any]) -> str:
    """Content address of one driver build: spec + hardware + fit hyperparams."""
    return _sha({
        "version": _ENTRY_VERSION,
        "spec": spec_fingerprint(spec),
        "hw": dataclasses.asdict(hw),
        "hyper": dict(sorted(hyper.items())),
    })


@dataclass
class CacheEntry:
    kernel: str
    key: str
    source: str                     # generated driver module source
    fits: dict                      # metric -> {function json + fit stats}
    stats: dict                     # probe counts / device seconds of the build
    created_at: float
    hw_name: str
    # Tuning generation: bumped by the telemetry refit loop so every process
    # in a fleet converges on the newest fit.  ``lookup_latest`` prefers the
    # highest generation; generation 0 is a plain compile-time build.
    tuning_version: int = 0

    def content_hash(self) -> str:
        payload: dict[str, Any] = {"source": self.source, "fits": self.fits}
        # Folded into the hash only when set, so generation-0 entries written
        # by older builds still verify; a tampered generation on a refit
        # entry invalidates it instead of pinning a stale fit as newest.
        if self.tuning_version:
            payload["tuning_version"] = self.tuning_version
        return _sha(payload)


@dataclass
class PlanEntry:
    """One compiled launch plan (core/plan.py LaunchPlanTable.to_json)."""

    kernel: str
    key: str
    hw_name: str
    plan: dict                      # LaunchPlanTable JSON payload
    created_at: float
    tuning_version: int = 0

    def content_hash(self) -> str:
        return _sha({"plan": self.plan,
                     "tuning_version": self.tuning_version})


class DriverCache:
    """On-disk, content-addressed store of generated driver artifacts."""

    def __init__(self, root: str | None = None):
        self.root = root or default_cache_dir()

    # -- paths ---------------------------------------------------------------
    def _kernel_dir(self, kernel: str) -> str:
        return os.path.join(self.root, kernel)

    def path(self, kernel: str, key: str) -> str:
        return os.path.join(self._kernel_dir(kernel), f"{key}.json")

    def plan_path(self, kernel: str, key: str) -> str:
        return os.path.join(self._kernel_dir(kernel), f"{key}.plan.json")

    # -- read ----------------------------------------------------------------
    def get(self, kernel: str, key: str) -> CacheEntry | None:
        """Entry for an exact build key, or None (miss / stale)."""
        return self._load(self.path(kernel, key), expect_key=key)

    def lookup_latest(self, kernel: str,
                      hw_name: str | None = None) -> CacheEntry | None:
        """Newest valid entry for a kernel (read-through path: the caller
        knows the kernel name but not the build hyperparams).

        "Newest" orders first by ``tuning_version`` -- a refit written by the
        telemetry loop outranks every older generation regardless of file
        times, which is what makes a whole fleet converge on the corrected
        fit -- then by build timestamp.  ``hw_name`` filters to entries tuned
        for that device: launch parameters optimal on one device are
        generally not on another (the paper's performance-portability point),
        so a mismatched entry must read as a miss, not a warm start.
        """
        best: CacheEntry | None = None
        for _, entry in self._entries(kernel, hw_name):
            if best is None or (entry.tuning_version, entry.created_at) > \
                    (best.tuning_version, best.created_at):
                best = entry
        return best

    def _entries(self, kernel: str, hw_name: str | None = None
                 ) -> list[tuple[str, CacheEntry]]:
        """All valid (path, entry) pairs for a kernel, hw-filtered."""
        d = self._kernel_dir(kernel)
        try:
            names = os.listdir(d)
        except OSError:
            return []
        out = []
        for f in sorted(names):
            if not f.endswith(".json") or f.endswith(".plan.json"):
                continue
            p = os.path.join(d, f)
            entry = self._load(p)
            if entry is not None and (hw_name is None
                                      or entry.hw_name == hw_name):
                out.append((p, entry))
        return out

    def latest_version(self, kernel: str,
                       hw_name: str | None = None) -> int:
        """Highest tuning generation stored for a kernel (0 if none)."""
        return max((e.tuning_version for _, e in self._entries(kernel,
                                                               hw_name)),
                   default=0)

    def _load(self, path: str, expect_key: str | None = None
              ) -> CacheEntry | None:
        try:
            with open(path) as f:
                raw = json.load(f)
            entry = CacheEntry(
                kernel=raw["kernel"], key=raw["key"], source=raw["source"],
                fits=raw["fits"], stats=raw.get("stats", {}),
                created_at=raw.get("created_at", 0.0),
                hw_name=raw.get("hw_name", ""),
                tuning_version=int(raw.get("tuning_version", 0)))
        except (OSError, ValueError, KeyError):
            return None
        # Stale-hash invalidation: stored payload must hash to the recorded
        # content hash, and the entry must live under the key it claims.
        if raw.get("content_hash") != entry.content_hash() or \
                (expect_key is not None and entry.key != expect_key):
            try:
                os.remove(path)
            except OSError:
                pass
            return None
        return entry

    # -- plan artifacts (compiled launch plans, core/plan.py) -----------------
    def get_plan(self, kernel: str, key: str) -> PlanEntry | None:
        """Plan entry for an exact plan key, or None (miss / stale)."""
        return self._load_plan(self.plan_path(kernel, key), expect_key=key)

    def lookup_latest_plan(self, kernel: str,
                           hw_name: str | None = None) -> PlanEntry | None:
        """Newest valid plan for a kernel, ordered like ``lookup_latest``:
        highest tuning generation first, then build timestamp."""
        best: PlanEntry | None = None
        for _, entry in self._plan_entries(kernel, hw_name):
            if best is None or (entry.tuning_version, entry.created_at) > \
                    (best.tuning_version, best.created_at):
                best = entry
        return best

    def _plan_entries(self, kernel: str, hw_name: str | None = None
                      ) -> list[tuple[str, PlanEntry]]:
        d = self._kernel_dir(kernel)
        try:
            names = os.listdir(d)
        except OSError:
            return []
        out = []
        for f in sorted(names):
            if not f.endswith(".plan.json"):
                continue
            p = os.path.join(d, f)
            entry = self._load_plan(p)
            if entry is not None and (hw_name is None
                                      or entry.hw_name == hw_name):
                out.append((p, entry))
        return out

    def _load_plan(self, path: str, expect_key: str | None = None
                   ) -> PlanEntry | None:
        try:
            with open(path) as f:
                raw = json.load(f)
            entry = PlanEntry(
                kernel=raw["kernel"], key=raw["key"],
                hw_name=raw.get("hw_name", ""), plan=raw["plan"],
                created_at=raw.get("created_at", 0.0),
                tuning_version=int(raw.get("tuning_version", 0)))
        except (OSError, ValueError, KeyError):
            return None
        if raw.get("content_hash") != entry.content_hash() or \
                (expect_key is not None and entry.key != expect_key):
            try:
                os.remove(path)
            except OSError:
                pass
            return None
        return entry

    def put_plan(self, entry: PlanEntry) -> str:
        d = self._kernel_dir(entry.kernel)
        os.makedirs(d, exist_ok=True)
        path = self.plan_path(entry.kernel, entry.key)
        raw = {
            "version": _ENTRY_VERSION,
            "kernel": entry.kernel,
            "key": entry.key,
            "hw_name": entry.hw_name,
            "plan": entry.plan,
            "created_at": entry.created_at or time.time(),
            "tuning_version": entry.tuning_version,
            "content_hash": entry.content_hash(),
        }
        _write_json_atomic(path, raw)
        return path

    # -- write ---------------------------------------------------------------
    def put(self, entry: CacheEntry) -> str:
        d = self._kernel_dir(entry.kernel)
        os.makedirs(d, exist_ok=True)
        path = self.path(entry.kernel, entry.key)
        raw = {
            "version": _ENTRY_VERSION,
            "kernel": entry.kernel,
            "key": entry.key,
            "source": entry.source,
            "fits": entry.fits,
            "stats": entry.stats,
            "created_at": entry.created_at or time.time(),
            "hw_name": entry.hw_name,
            "tuning_version": entry.tuning_version,
            "content_hash": entry.content_hash(),
        }
        _write_json_atomic(path, raw)   # readers never see halves
        return path

    # -- maintenance ----------------------------------------------------------
    def invalidate(self, kernel: str, hw_name: str | None = None,
                   below_version: int | None = None) -> int:
        """Delete entries for a kernel; returns how many were removed.

        ``below_version`` keeps entries at that tuning generation or newer --
        the invalidate-on-refit path: once the telemetry loop has written a
        corrected generation-N fit, generations < N are evicted so no process
        can warm-start from the fit that drifted.  ``hw_name`` scopes the
        eviction to one device's artifacts.  Compiled launch plans are
        evicted under the same rule: a plan is frozen output of its fit
        generation and must never outlive it.
        """
        removed = 0
        doomed = [
            (path, entry.tuning_version)
            for path, entry in (self._entries(kernel, hw_name)
                                + self._plan_entries(kernel, hw_name))]
        for path, version in doomed:
            if below_version is not None and version >= below_version:
                continue
            try:
                os.remove(path)
                removed += 1
            except OSError:
                pass            # a concurrent worker already evicted it
        return removed

    def kernels(self) -> list[str]:
        if not os.path.isdir(self.root):
            return []
        return sorted(
            k for k in os.listdir(self.root)
            if os.path.isdir(self._kernel_dir(k)))

    def clear(self) -> None:
        for kernel in self.kernels():
            d = self._kernel_dir(kernel)
            for f in os.listdir(d):
                try:
                    os.remove(os.path.join(d, f))
                except OSError:
                    pass
            try:
                os.rmdir(d)
            except OSError:
                pass


def default_cache() -> DriverCache:
    """Process default cache (re-reads $KLARAPTOR_CACHE_DIR on every call so
    tests and multi-tenant jobs can redirect it)."""
    return DriverCache()
