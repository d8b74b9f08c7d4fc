"""What every Pallas kernel here hands Mosaic, and when kernels interpret.

One VMEM budget serves the tuner and the compiler: ``compiler_params``
passes the target chip's ``HardwareParams.vmem_limit_bytes`` as the
kernel's scoped VMEM limit, and the tuner's feasibility mask admits only
configs whose modelled footprint fits the same number
(``KernelSpec.feasible_mask``).
"""

from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

from repro.core.device_model import target_hardware

__all__ = ["compiler_params", "interpret_mode"]


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    """Mosaic parameters for one kernel: its grid's dimension semantics and
    the target chip's scoped VMEM budget."""
    return pltpu.CompilerParams(
        dimension_semantics=dimension_semantics,
        vmem_limit_bytes=target_hardware().vmem_limit_bytes)


def interpret_mode(requested: bool = True) -> bool:
    """Whether a kernel runs in the Pallas interpreter: only when requested
    and the default backend is the CPU (the test path).  On a TPU every
    kernel compiles, whatever its caller passes."""
    return requested and jax.default_backend() == "cpu"
