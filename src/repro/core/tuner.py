"""KLARAPTOR facade: build drivers (compile time) and evaluate them.

``Klaraptor.build_driver`` runs the three compile-time steps of Section IV
(collect -> fit -> codegen) for one kernel spec against a device oracle and
returns a ready ``DriverProgram``.  Builds write through the persistent
driver-artifact cache (core/cache.py): a second process asking for the same
(spec, hardware, fit hyperparameters -- including the probe-selection
strategy and budget) gets the stored driver back without probing the device
at all.

``exhaustive_search`` is the paper's comparison baseline (Table I "Best
Config." column): evaluate *every* feasible configuration at the actual data
size -- in one batched oracle pass over the candidate table -- and take the
argmin of true execution time.  ``selection_ratio`` scores a driver the way
Fig. 1 does: best_time / chosen_time (>= 0.85 is "good").

``search_best`` is the cheap online middle ground: a budget-aware
repro.search strategy probes a capped fraction of the candidate table at the
*actual* data size -- for untuned kernels where neither a driver nor the
exhaustive baseline is affordable.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Mapping, Sequence

import numpy as np

from repro.trace import trace_span

from .cache import CacheEntry, DriverCache, cache_key, default_cache
from .codegen import generate_driver_source
from .collect import CollectedData, collect
from .device_model import DeviceModel, HardwareParams, V5E, V5eSimulator
from .driver import DriverProgram, register_driver
from .fitting import FitResult, fit_auto
from .kernel_spec import CandidateTable, KernelSpec
from .perf_model import LOW_LEVEL_METRICS, build_time_program
from .rational import RationalFunction

__all__ = ["BuildResult", "Klaraptor", "exhaustive_search", "search_best",
           "selection_ratio"]

logger = logging.getLogger(__name__)

Dims = Mapping[str, int]


@dataclass
class BuildResult:
    driver: DriverProgram
    fits: dict[str, FitResult]
    collected: CollectedData
    build_wall_seconds: float
    probe_device_seconds: float
    from_cache: bool = False

    def fit_report(self) -> str:
        origin = " (cached)" if self.from_cache else ""
        lines = [f"driver build for {self.driver.kernel}{origin}:"]
        for m, f in self.fits.items():
            lines.append(
                f"  {m}: deg(num)={f.num_bounds} deg(den)={f.den_bounds} "
                f"params={f.n_params} rel_err={f.rel_error:.3f} "
                f"cv_err={f.cv_error:.3f}")
        lines.append(
            f"  probes={self.collected.n_probe_executions} "
            f"device_s={self.probe_device_seconds:.4f} "
            f"wall_s={self.build_wall_seconds:.2f}")
        return "\n".join(lines)


def _fits_to_json(fits: dict[str, FitResult]) -> dict:
    return {m: {
        "function": f.function.to_json(),
        "rel_error": f.rel_error,
        "cv_error": f.cv_error,
        "num_bounds": list(f.num_bounds),
        "den_bounds": list(f.den_bounds),
        "n_params": f.n_params,
        "condition_number": f.condition_number,
    } for m, f in fits.items()}


def _fits_from_json(raw: dict) -> dict[str, FitResult]:
    out = {}
    for m, f in raw.items():
        out[m] = FitResult(
            function=RationalFunction.from_json(f["function"]),
            rel_error=f["rel_error"],
            cv_error=f["cv_error"],
            num_bounds=tuple(f["num_bounds"]),
            den_bounds=tuple(f["den_bounds"]),
            n_params=f["n_params"],
            condition_number=f["condition_number"],
        )
    return out


class Klaraptor:
    """The tool: compile-time driver construction + runtime selection."""

    def __init__(self, device: DeviceModel | None = None,
                 hw: HardwareParams = V5E,
                 cache: DriverCache | None | bool = None):
        self.device = device or V5eSimulator(hw)
        self.hw = hw
        # cache=False disables persistence; None selects the default store.
        self.cache: DriverCache | None
        if cache is False:
            self.cache = None
        elif cache is None or cache is True:
            self.cache = default_cache()
        else:
            self.cache = cache

    def build_driver(
        self,
        spec: KernelSpec,
        probe_data: Sequence[Dims] | None = None,
        repeats: int = 3,
        max_configs_per_size: int = 32,
        seed: int = 0,
        register: bool = True,
        max_num_degree: int = 2,
        max_den_degree: int = 2,
        use_cache: bool = True,
        strategy=None,
        budget=None,
        cache_version: int = 0,
        shard_rows: int | None = None,
        data: CollectedData | None = None,
    ) -> BuildResult:
        """Collect -> fit -> codegen one driver (cache-aware).

        ``data`` (optional) supplies an already-collected dataset -- the
        fleet merge layer's write-through path: the probe hyperparameters
        must still describe how it was collected, so the cache key is
        identical to the single-process build the farm replaced.
        ``shard_rows`` selects chunk-seeded probe noise (see
        ``collect``); it is part of the build identity when set.
        """
        from repro.search import SearchBudget, resolve_strategy

        t0 = time.perf_counter()
        strategy = resolve_strategy(strategy)
        if budget is not None and not isinstance(budget, SearchBudget):
            raise TypeError(
                f"budget must be a repro.search.SearchBudget, got "
                f"{type(budget).__name__}")
        if data is not None and data.spec_name != spec.name:
            raise ValueError(
                f"supplied data is for {data.spec_name!r}, not {spec.name!r}")
        hyper = {
            "repeats": repeats,
            "max_configs_per_size": max_configs_per_size,
            "seed": seed,
            "max_num_degree": max_num_degree,
            "max_den_degree": max_den_degree,
            "probe_data": [sorted(d.items()) for d in probe_data]
            if probe_data is not None else None,
            # probing a different oracle (other device class, other
            # simulator noise/seed) must not hit this build's artifact
            "device": self.device.fingerprint(),
            # probe selection is part of the build identity: a different
            # strategy or budget collects different data -> different artifact
            "strategy": strategy.fingerprint(),
            "budget": budget.fingerprint() if budget is not None else None,
        }
        # Folded in only when set, so pre-existing builds keep their keys.
        if shard_rows is not None:
            hyper["shard_rows"] = int(shard_rows)
        key = cache_key(spec, self.hw, hyper) if self.cache else None

        with trace_span("build_driver", kernel=spec.name) as bsp:
            if self.cache is not None and use_cache and key is not None:
                entry = self.cache.get(spec.name, key)
                if entry is not None:
                    driver = DriverProgram.from_source(
                        spec.name, entry.source, self.hw)
                    if register:
                        register_driver(driver)
                    bsp.set(from_cache=True)
                    return BuildResult(
                        driver=driver,
                        fits=_fits_from_json(entry.fits),
                        collected=CollectedData.empty(spec, **entry.stats),
                        build_wall_seconds=time.perf_counter() - t0,
                        probe_device_seconds=0.0,
                        from_cache=True,
                    )

            if data is None:
                data = collect(
                    spec, self.device,
                    probe_data=probe_data, hw=self.hw, repeats=repeats,
                    max_configs_per_size=max_configs_per_size, seed=seed,
                    strategy=strategy, budget=budget,
                    shard_rows=shard_rows,
                )
            fits: dict[str, FitResult] = {}
            with trace_span("fit", kernel=spec.name,
                            n_samples=len(data)) as fsp:
                for metric in LOW_LEVEL_METRICS:
                    vars_ = spec.metric_fit_vars(metric)
                    X, y = data.matrix(metric, vars_)
                    fits[metric] = fit_auto(
                        X, y, vars_,
                        max_num_degree=max_num_degree,
                        max_den_degree=max_den_degree,
                    )
                fsp.set(rel_error={m: round(f.rel_error, 6)
                                   for m, f in fits.items()})
            with trace_span("codegen", kernel=spec.name):
                program = build_time_program(
                    spec, {m: f.function for m, f in fits.items()}, self.hw)
                source = generate_driver_source(
                    spec, program,
                    {m: f.function for m, f in fits.items()}, self.hw)
                driver = DriverProgram.from_source(
                    spec.name, source, self.hw)
            if register:
                register_driver(driver)
            if self.cache is not None and key is not None:
                self._cache_put(spec, key, source, fits, data,
                                tuning_version=cache_version)
            bsp.set(from_cache=False,
                    probe_device_seconds=data.probe_device_seconds)
            return BuildResult(
                driver=driver,
                fits=fits,
                collected=data,
                build_wall_seconds=time.perf_counter() - t0,
                probe_device_seconds=data.probe_device_seconds,
            )

    # One-time flag for the best-effort cache-write warning (class-wide: a
    # read-only serving node should log the diagnosis once, not per build).
    _cache_write_warned = False

    def _cache_put(self, spec: KernelSpec, key: str, source: str,
                   fits: dict[str, FitResult], data: CollectedData,
                   tuning_version: int = 0) -> None:
        # Persistence is best-effort: an unwritable cache dir (read-only
        # serving node) must not fail the build itself.
        try:
            self.cache.put(CacheEntry(
                kernel=spec.name,
                key=key,
                source=source,
                fits=_fits_to_json(fits),
                stats={
                    "n_probe_executions": data.n_probe_executions,
                    "probe_device_seconds": data.probe_device_seconds,
                    "collect_wall_seconds": data.collect_wall_seconds,
                },
                created_at=time.time(),
                hw_name=self.hw.name,
                tuning_version=tuning_version,
            ))
        except OSError as e:
            if not Klaraptor._cache_write_warned:
                Klaraptor._cache_write_warned = True
                logger.warning(
                    "driver-artifact cache write failed (%s) at %s for "
                    "kernel %s; builds will not persist -- every process "
                    "re-pays the probe cost (set KLARAPTOR_CACHE_DIR to a "
                    "writable path)", e, self.cache.path(spec.name, key),
                    spec.name)


def exhaustive_search(
    spec: KernelSpec,
    device: DeviceModel,
    D: Dims,
    hw: HardwareParams = V5E,
) -> tuple[dict[str, int], float, int, float]:
    """Ground-truth argmin over every feasible config at data size D.

    One batched oracle evaluation over the whole candidate table (no inner
    loop).  Returns (best_P, best_time, n_evaluations, total_device_seconds).
    total_device_seconds is what an actual exhaustive search would spend
    running the kernel -- the Fig. 3 cost of the baseline.
    """
    table = spec.candidates(D, hw)
    if not len(table):
        raise ValueError(f"no feasible configuration for {spec.name} at {D}")
    times = device.true_time_batch(spec.traffic_table(D, table, hw))
    best = int(np.argmin(times))
    return (table.row(best), float(times[best]), len(table),
            float(np.sum(times)))


def search_best(
    spec: KernelSpec,
    device: DeviceModel,
    D: Dims,
    strategy=None,
    budget=None,
    hw: HardwareParams = V5E,
    seed: int = 0,
):
    """Budget-aware online search at the actual data size D.

    The cheap alternative to ``exhaustive_search`` for untuned kernels: a
    repro.search strategy (name or instance; default stratified ``random``)
    probes the candidate table under a hard ``SearchBudget`` (default ~25%
    of a one-repeat exhaustive pass) and the observed argmin is returned as
    a ``SearchResult`` (``.best_config`` is the chosen P).
    """
    from repro.search import run_search

    return run_search(spec, device, D, strategy=strategy, budget=budget,
                      hw=hw, seed=seed)


def selection_ratio(
    spec: KernelSpec,
    device: DeviceModel,
    driver: DriverProgram,
    D: Dims,
    hw: HardwareParams = V5E,
) -> dict:
    """Fig. 1 metric: best_time / chosen_time at data size D (1.0 = optimal)."""
    chosen = driver.choose(D)
    one = CandidateTable.from_rows(spec.program_params, [chosen])
    t_chosen = float(device.true_time_batch(spec.traffic_table(D, one, hw))[0])
    best_P, t_best, n, _ = exhaustive_search(spec, device, D, hw)
    return {
        "kernel": spec.name,
        "D": dict(D),
        "chosen": chosen,
        "chosen_time_s": t_chosen,
        "best": best_P,
        "best_time_s": t_best,
        "ratio": t_best / max(t_chosen, 1e-300),
        "n_configs": n,
    }
