"""Driver programs: runtime side of KLARAPTOR (paper Section IV, steps 4-6).

A ``DriverProgram`` wraps the generated rational-program module for one
kernel.  It is what ``kernels/ops.py`` calls immediately before each Pallas
launch -- the IO-builder contract of Section V-C: data parameter values in,
six integers (grid + block) out; here, the BlockSpec tile dict out.

A process-wide registry maps kernel-spec names to built drivers so that model
code can ask for tuned launch parameters with one call.  The registry *reads
through* the persistent driver-artifact cache (core/cache.py): a driver built
by any earlier process is loaded from disk on first use instead of being
rebuilt -- the warm-start path that lets serving fleets share tuning work.
Decisions are memoized inside the generated module (its _HISTORY table),
the paper's per-invocation decision history.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Mapping

import numpy as np

from repro.trace import trace_span

from .codegen import compile_driver_module
from .device_model import HardwareParams, V5E

__all__ = ["ChoiceEvent", "DriverProgram", "WarmStartSummary", "registry",
           "register_driver", "get_driver", "choose_or_default",
           "set_choice_listener", "get_choice_listener",
           "warm_start_from_cache", "fit_tile"]


@functools.lru_cache(maxsize=4096)
def fit_tile(size: int, tile: int, align: int) -> int:
    """Largest divisor of ``size`` that is <= tile and a multiple of
    ``align`` -- keeps tuned tiles valid for shapes the tuner never saw.

    The canonical tile-snapping helper shared by every dispatch layer
    (``kernels/ops.py`` for hand-specced ops, ``introspect.AutoKernel``
    with its derived granularities).  Memoized: the O(tile/align)
    scan-down loop would otherwise re-run on every trace-time dispatch,
    and (size, tile, align) triples recur heavily under steady traffic.
    """
    tile = min(tile, size)
    t = (tile // align) * align
    while t > align and size % t:
        t -= align
    if t >= align and size % t == 0:
        return t
    return size  # degenerate: single block

logger = logging.getLogger(__name__)

Dims = Mapping[str, int]


@dataclass(frozen=True)
class ChoiceEvent:
    """One launch-parameter decision, as seen by the telemetry listener.

    ``source`` names the path that produced the config: ``"override"`` (a
    telemetry-pinned per-shape config), ``"driver"`` (the rational program
    chose), ``"search"``/``"search_memo"`` (the online-search escalation),
    or ``"default"`` (fell back to the static heuristic).  ``predicted_s``
    is the driver's rational-program time estimate for the returned config
    -- the prediction that runtime observability checks against observed
    launches -- and is only computed when a listener is installed.

    ``t_ns`` is a monotonic-clock stamp (``time.monotonic_ns``) taken at
    emission so the flight ledger and traces can order events without
    wall-clock skew.  It is only read -- and the clock only consulted --
    when a listener is installed; the no-listener path stays zero-overhead.
    """

    kernel: str
    D: dict
    config: dict
    source: str
    predicted_s: float | None
    hw_name: str
    t_ns: int | None = None


# Process-wide choice listener (repro.telemetry installs itself here).  A
# plain module global, not a registry field: the hook must survive
# ``registry.clear()`` in tests and cost one ``is None`` check per launch
# when unused.
_choice_listener: Callable[[ChoiceEvent], None] | None = None
_listener_error_warned = False


def set_choice_listener(
        listener: Callable[[ChoiceEvent], None] | None) -> None:
    """Install (or with None remove) the process-wide choice listener.

    The listener is invoked after every ``choose_or_default`` decision.  It
    must be cheap; anything it raises is swallowed (with a one-time warning)
    because observability must never take down the serving path.
    """
    global _choice_listener
    _choice_listener = listener


def get_choice_listener() -> Callable[[ChoiceEvent], None] | None:
    return _choice_listener


def _notify(kernel: str, D: Dims, config: dict, source: str,
            predicted_s: float | None, hw: HardwareParams) -> None:
    global _listener_error_warned
    if _choice_listener is None:
        return
    try:
        _choice_listener(ChoiceEvent(
            kernel=kernel, D=dict(D), config=dict(config), source=source,
            predicted_s=predicted_s, hw_name=hw.name,
            t_ns=time.monotonic_ns()))
    except Exception:
        if not _listener_error_warned:
            _listener_error_warned = True
            logger.warning(
                "choice listener raised; telemetry for this process is "
                "unreliable (further listener errors are suppressed)",
                exc_info=True)


@dataclass
class DriverProgram:
    kernel: str
    source: str
    namespace: dict = field(repr=False)
    hw: HardwareParams = V5E

    @classmethod
    def from_source(cls, kernel: str, source: str,
                    hw: HardwareParams = V5E) -> "DriverProgram":
        return cls(kernel=kernel, source=source,
                   namespace=compile_driver_module(source), hw=hw)

    @property
    def data_params(self) -> tuple[str, ...]:
        return tuple(self.namespace["DATA_PARAMS"])

    @property
    def program_params(self) -> tuple[str, ...]:
        return tuple(self.namespace["PROGRAM_PARAMS"])

    # -- step 4: rational program evaluation ---------------------------------
    def estimate(self, D: Dims, P: Dims) -> float:
        return float(self.namespace["estimate"](**{**D, **P}))

    def estimate_batch(self, D: Dims,
                       columns: Mapping[str, np.ndarray]) -> np.ndarray:
        """Vectorized E over a columnar candidate table: one ndarray pass."""
        est = self.namespace["estimate"](**{**D, **columns})
        return np.asarray(est, dtype=np.float64)

    def candidates(self, D: Dims) -> dict[str, np.ndarray]:
        """Columnar feasible table: one int64 ndarray per program param."""
        return self.namespace["candidates"](**D)

    # -- steps 5-6: selection (memoized) --------------------------------------
    def choose(self, D: Dims, margin: float = 0.02) -> dict[str, int]:
        return self.namespace["choose"](**D, margin=margin)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.source)

    @classmethod
    def load(cls, kernel: str, path: str,
             hw: HardwareParams = V5E) -> "DriverProgram":
        with open(path) as f:
            return cls.from_source(kernel, f.read(), hw)


# Distinguishes "never searched" from "searched and failed" in the memo.
_MISS = object()


def _fresh_stats() -> dict[str, int]:
    return {"disk_cache_hits": 0, "disk_cache_misses": 0}


class _Registry:
    """Process-wide driver registry consulted by kernels/ops.py."""

    def __init__(self) -> None:
        self._drivers: dict[str, DriverProgram] = {}
        self._cache_misses: set[tuple[str, str]] = set()
        self._searched: dict[tuple, dict[str, int]] = {}
        self._overrides: dict[tuple, dict[str, int]] = {}
        # Bumped by every mutation that could change a launch decision
        # (driver registration, refit invalidation, pinned override): a
        # gauge for the exporter, and how tools tell that serving state
        # was left untouched.
        self._generation = 0
        self._stats = _fresh_stats()
        self._lock = threading.Lock()

    @property
    def generation(self) -> int:
        return self._generation

    def register(self, driver: DriverProgram) -> None:
        with self._lock:
            self._drivers[driver.kernel] = driver
            self._cache_misses = {k for k in self._cache_misses
                                  if k[0] != driver.kernel}
            self._generation += 1

    def get(self, kernel: str) -> DriverProgram | None:
        return self._drivers.get(kernel)

    # Negative memo for the disk read-through: an untuned kernel must cost
    # one dict lookup per launch, not filesystem I/O.  Keyed by (kernel,
    # hw name) since the cache lookup is hardware-scoped.
    def note_cache_miss(self, kernel: str, hw_name: str) -> None:
        with self._lock:
            self._cache_misses.add((kernel, hw_name))

    def known_cache_miss(self, kernel: str, hw_name: str) -> bool:
        return (kernel, hw_name) in self._cache_misses

    # Memo for the online-search escalation: searching costs real device
    # time, so a (kernel, hw, D) triple is searched at most once per process.
    # ``config=None`` records a *failed* search (infeasible / budget too
    # small) -- retrying it every launch would re-pay the enumeration cost.
    def note_searched(self, key: tuple,
                      config: dict[str, int] | None) -> None:
        with self._lock:
            self._searched[key] = config

    def searched(self, key: tuple):
        """Stored config, None for a memoized failure, _MISS if unseen."""
        return self._searched.get(key, _MISS)

    # Per-shape pinned configs, set by the telemetry refit loop when a live
    # probe showed a specific config observably faster than the (possibly
    # still imperfect) refitted driver's choice at that exact shape.  An
    # override outranks the driver: it is measured evidence, the driver is a
    # model.  Overrides are process-local; fleet convergence goes through the
    # versioned artifact cache.
    @staticmethod
    def _override_key(kernel: str, hw_name: str, D: Dims) -> tuple:
        return (kernel, hw_name, tuple(sorted(D.items())))

    def note_override(self, kernel: str, hw_name: str, D: Dims,
                      config: dict[str, int]) -> None:
        with self._lock:
            self._overrides[self._override_key(kernel, hw_name, D)] = \
                dict(config)
            self._generation += 1

    def override(self, kernel: str, hw_name: str,
                 D: Dims) -> dict[str, int] | None:
        return self._overrides.get(self._override_key(kernel, hw_name, D))

    def note_disk_cache(self, hit: bool) -> None:
        with self._lock:
            self._stats["disk_cache_hits" if hit
                        else "disk_cache_misses"] += 1

    def stats(self) -> dict[str, int]:
        """Snapshot of the registry's disk read-through counters."""
        with self._lock:
            return dict(self._stats)

    def invalidate_kernel(self, kernel: str) -> None:
        """Forget everything memoized for one kernel (the hot-swap path).

        A refit is about to register a corrected driver: the old driver, the
        negative disk-read memo, every searched-shape memo and every pinned
        override for the kernel describe the *previous* fit and must not
        outlive it.
        """
        with self._lock:
            self._drivers.pop(kernel, None)
            self._cache_misses = {k for k in self._cache_misses
                                  if k[0] != kernel}
            self._searched = {k: v for k, v in self._searched.items()
                              if k[0] != kernel}
            self._overrides = {k: v for k, v in self._overrides.items()
                               if k[0] != kernel}
            self._generation += 1

    def clear(self) -> None:
        with self._lock:
            self._drivers.clear()
            self._cache_misses.clear()
            self._searched.clear()
            self._overrides.clear()
            self._generation += 1
            self._stats = _fresh_stats()

    def kernels(self) -> list[str]:
        return sorted(self._drivers)


registry = _Registry()


def register_driver(driver: DriverProgram) -> None:
    registry.register(driver)


# One-time flag: a cache entry whose source no longer compiles (written by
# an older code version, or damaged in a way that still matches its content
# hash) is diagnosed once, then silently skipped.
_bad_entry_warned = False


def _driver_from_entry(kernel: str, entry, hw: HardwareParams
                       ) -> DriverProgram | None:
    """Build a driver from a cache entry, tolerating corrupted sources.

    ``cache._load`` already rejects truncated/tampered payloads via the
    content hash; what reaches here can still fail to *compile* (e.g. an
    artifact from an incompatible code version).  One bad artifact must not
    take down a serving process at startup, so the failure is a one-time
    ``logging.warning`` and a skip, never a raise.
    """
    global _bad_entry_warned
    try:
        return DriverProgram.from_source(kernel, entry.source, hw)
    except Exception as e:
        if not _bad_entry_warned:
            _bad_entry_warned = True
            logger.warning(
                "cached driver artifact for kernel %s (key %s...) failed to "
                "load (%s: %s); skipping it -- further bad artifacts are "
                "skipped silently", kernel, entry.key[:12],
                type(e).__name__, e)
        return None


def get_driver(kernel: str, read_cache: bool = True,
               hw: HardwareParams = V5E) -> DriverProgram | None:
    """Registered driver for ``kernel``; on a registry miss, fall back to the
    persistent artifact cache (a driver built in another process is loaded,
    not rebuilt) and register the loaded driver for subsequent calls.

    Only entries tuned for ``hw`` are loaded -- a driver built for another
    device would silently choose wrong launch parameters.  Disk misses are
    memoized so untuned kernels stay one dict lookup per launch.
    """
    drv = registry.get(kernel)
    if drv is not None or not read_cache:
        return drv
    if registry.known_cache_miss(kernel, hw.name):
        return None
    from .cache import default_cache

    entry = default_cache().lookup_latest(kernel, hw_name=hw.name)
    drv = (_driver_from_entry(kernel, entry, hw)
           if entry is not None else None)
    if drv is None:
        registry.note_cache_miss(kernel, hw.name)
        registry.note_disk_cache(hit=False)
        return None
    registry.register(drv)
    registry.note_disk_cache(hit=True)
    return drv


class WarmStartSummary(list):
    """Loaded kernel names (a plain list, for compatibility) plus warm-start
    coverage counts: how many kernels were skipped because no artifact
    matched (``skipped_no_entry``), failed to load (``skipped_bad``), or
    were already registered (``already_registered``)."""

    def __init__(self, loaded: list[str] | None = None) -> None:
        super().__init__(loaded or [])
        self.already_registered = 0
        self.skipped_no_entry = 0
        self.skipped_bad = 0

    @property
    def loaded(self) -> list[str]:
        return list(self)

    def as_dict(self) -> dict:
        return {
            "loaded": list(self),
            "already_registered": self.already_registered,
            "skipped_no_entry": self.skipped_no_entry,
            "skipped_bad": self.skipped_bad,
        }

    def __repr__(self) -> str:
        return (f"WarmStartSummary(loaded={list(self)!r}, "
                f"already_registered={self.already_registered}, "
                f"skipped_no_entry={self.skipped_no_entry}, "
                f"skipped_bad={self.skipped_bad})")


def warm_start_from_cache(kernels: list[str] | None = None,
                          hw: HardwareParams = V5E) -> WarmStartSummary:
    """Pre-load cached drivers into the registry (serving-process startup).

    ``kernels=None`` loads every kernel present in the cache.  Kernels
    already registered are left untouched; entries tuned for a different
    device than ``hw``, and entries whose stored source fails to load
    (one-time warning), are skipped.  Returns a ``WarmStartSummary``: the
    loaded names (list-compatible) plus loaded/skipped coverage counts, so
    serving processes and benchmarks can report how much of the fleet's
    tuning work they inherited.
    """
    from .cache import default_cache

    cache = default_cache()
    names = kernels if kernels is not None else cache.kernels()
    summary = WarmStartSummary()
    with trace_span("warm_start", hw=hw.name) as sp:
        for name in names:
            if registry.get(name) is not None:
                summary.already_registered += 1
                continue
            entry = cache.lookup_latest(name, hw_name=hw.name)
            if entry is None:
                summary.skipped_no_entry += 1
                continue
            drv = _driver_from_entry(name, entry, hw)
            if drv is None:
                summary.skipped_bad += 1
            else:
                registry.register(drv)
                summary.append(name)
        sp.set(loaded=len(summary),
               skipped_no_entry=summary.skipped_no_entry,
               skipped_bad=summary.skipped_bad)
    return summary


def choose_or_default(kernel: str, D: Dims,
                      default: dict[str, int],
                      hw: HardwareParams = V5E,
                      *,
                      spec=None,
                      device=None,
                      strategy=None,
                      budget=None) -> dict[str, int]:
    """Tuned launch parameters if a driver or cache entry covers the shape,
    else ``default`` -- or, opt-in, a budgeted online search.

    Dispatch order: a telemetry-pinned per-shape override (measured
    evidence) outranks everything; then the driver's vectorized
    rational-program evaluation (memoized in the generated module's own
    decision history); then the search escalation or the static default.

    This keeps model code runnable before any tuning has happened (the
    untuned path uses the static heuristic config, like un-instrumented CUDA
    uses whatever the programmer hard-coded).  A driver built for different
    data parameters raises KeyError on the missing names; an infeasible D
    raises ValueError -- both fall back to the default config rather than
    crash the untuned path.  ``hw`` scopes the cache read-through: only
    artifacts tuned for that device warm-start.

    Escalation path: passing ``spec`` *and* ``device`` opts in to running
    ``search_best`` when no driver exists -- or when the registered driver
    is stale/mismatched and raises -- so a budget-aware strategy (see
    repro.search) probes the actual data size instead of silently using the
    static default.  Results are memoized per (kernel, hw, D, strategy
    fingerprint, budget fingerprint) in the registry, so each shape pays the
    search at most once per process *per search configuration* -- switching
    strategies or raising the budget at runtime triggers a fresh search
    instead of being silently ignored; a failed search still falls back to
    ``default``.

    Every decision is reported to the process-wide choice listener
    (``set_choice_listener``; installed by ``repro.telemetry``) together
    with the driver's predicted time for the returned config, which is what
    the drift detector compares against sampled observed launches.
    Telemetry-pinned per-shape overrides (measured evidence from a refit
    pass) outrank the driver's model-based choice.
    """
    drv = get_driver(kernel, hw=hw)
    override = registry.override(kernel, hw.name, D)
    if override is not None:
        if _choice_listener is not None:
            pred = None
            if drv is not None:
                try:
                    pred = drv.estimate(D, override)
                except Exception:
                    pred = None
            _notify(kernel, D, override, "override", pred, hw)
        return dict(override)
    if drv is not None:
        try:
            cfg = drv.choose(D)
        except (ValueError, KeyError, TypeError):
            cfg = None  # stale/mismatched driver: search if opted in, else
        if cfg is not None:
            if _choice_listener is not None:
                # The prediction is telemetry garnish: a driver whose
                # estimate() breaks must still serve its valid choice.
                try:
                    pred = drv.estimate(D, cfg)
                except Exception:
                    pred = None
                _notify(kernel, D, cfg, "driver", pred, hw)
            return cfg
    if spec is None and device is None:
        if _choice_listener is not None:
            _notify(kernel, D, default, "default", None, hw)
        return dict(default)
    if spec is None or device is None:
        # Half an opt-in is a caller bug: silently running untuned would
        # hide it (same principle as the strategy-name resolution below).
        raise ValueError(
            "choose_or_default search escalation needs BOTH spec and "
            "device; got only "
            + ("spec" if device is None else "device"))
    from repro.search import SearchBudget, resolve_strategy

    from .tuner import search_best

    # Resolve outside the try: a typo'd strategy name is a configuration
    # error that must surface, not silently fall back to the default.
    strategy = resolve_strategy(strategy)
    if budget is not None and not isinstance(budget, SearchBudget):
        raise TypeError(
            f"budget must be a repro.search.SearchBudget, got "
            f"{type(budget).__name__}")
    # The memo is scoped by strategy and budget: a failure under a tiny
    # budget (or a result from a weak strategy) must not be served to a
    # caller asking for a different search.
    memo_key = (kernel, hw.name, tuple(sorted(D.items())),
                tuple(sorted(strategy.fingerprint().items())),
                tuple(sorted(budget.fingerprint().items()))
                if budget is not None else None)
    hit = registry.searched(memo_key)
    if hit is not _MISS:
        if hit is None:
            _notify(kernel, D, default, "default", None, hw)
            return dict(default)
        _notify(kernel, D, hit, "search_memo", None, hw)
        return dict(hit)
    try:
        result = search_best(spec, device, D, strategy=strategy,
                             budget=budget, hw=hw)
    except ValueError:            # infeasible D: no candidates to search
        registry.note_searched(memo_key, None)
        _notify(kernel, D, default, "default", None, hw)
        return dict(default)
    if result.best_config is None:   # budget too small to fit one probe
        registry.note_searched(memo_key, None)
        _notify(kernel, D, default, "default", None, hw)
        return dict(default)
    registry.note_searched(memo_key, result.best_config)
    _notify(kernel, D, result.best_config, "search", None, hw)
    return dict(result.best_config)
