"""KLARAPTOR core: rational programs for dynamic launch-parameter selection.

Public API re-exports.  See DESIGN.md for the paper-to-TPU mapping.
"""

from .cache import (
    CacheEntry, DriverCache, cache_key, default_cache,
    default_cache_dir, spec_fingerprint,
)
from .device_model import (
    DEVICE_KINDS, DTYPE_BYTES, V5E, V5P, DeviceModel, HardwareParams,
    KernelTraffic, ProbeBatch, ProbeRecord, RowProbe, TrafficOperand,
    TrafficTable, V5eSimulator, dtype_bytes, hardware_for, target_hardware,
)
from .driver import (
    ChoiceEvent, DriverProgram, WarmStartSummary, choose_or_default,
    get_choice_listener, get_driver, register_driver, registry,
    set_choice_listener, warm_start_from_cache,
)
from .fitting import FitResult, fit_auto, fit_polynomial, fit_rational
from .kernel_spec import (
    CandidateTable, GridAxis, KernelSpec, Operand, SpecError,
    flash_attention_spec, matmul_spec, moe_gmm_spec, polybench_suite,
    ssd_scan_spec,
)
from .occupancy import cuda_occupancy_program, tpu_pipeline_occupancy_program
from .perf_model import LOW_LEVEL_METRICS, build_time_program
from .polynomial import Polynomial, design_matrix, monomial_exponents
from .rational import RationalFunction
from .rational_program import (
    BinOp, Ceil, Const, Expr, Fitted, Floor, Max, Min, RationalProgram,
    Select, Var, ceil_div, const, floor_div, specialize_expr, var,
)
from .tuner import (
    BuildResult, Klaraptor, exhaustive_search, search_best, selection_ratio,
)

__all__ = [
    "CacheEntry", "DriverCache", "cache_key", "default_cache",
    "default_cache_dir", "spec_fingerprint",
    "DEVICE_KINDS", "DTYPE_BYTES", "V5E", "V5P", "DeviceModel",
    "HardwareParams", "KernelTraffic", "ProbeBatch", "ProbeRecord",
    "RowProbe", "TrafficOperand", "TrafficTable", "V5eSimulator",
    "dtype_bytes", "hardware_for", "target_hardware",
    "ChoiceEvent", "DriverProgram", "WarmStartSummary", "choose_or_default",
    "get_choice_listener", "get_driver", "register_driver",
    "registry", "set_choice_listener", "warm_start_from_cache",
    "FitResult", "fit_auto", "fit_polynomial", "fit_rational",
    "CandidateTable", "GridAxis", "KernelSpec", "Operand", "SpecError",
    "flash_attention_spec",
    "matmul_spec", "moe_gmm_spec", "polybench_suite", "ssd_scan_spec",
    "cuda_occupancy_program", "tpu_pipeline_occupancy_program",
    "LOW_LEVEL_METRICS", "build_time_program",
    "Polynomial", "design_matrix", "monomial_exponents",
    "RationalFunction",
    "BinOp", "Ceil", "Const", "Expr", "Fitted", "Floor", "Max", "Min",
    "RationalProgram", "Select", "Var", "ceil_div", "const", "floor_div",
    "specialize_expr", "var",
    "BuildResult", "Klaraptor", "exhaustive_search", "search_best",
    "selection_ratio",
]
