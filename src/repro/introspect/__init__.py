"""repro.introspect: automatic KernelSpec extraction from Pallas kernels.

The Pallas analogue of KLARAPTOR's LLVM pass (paper Section V-B): instead
of hand-writing the (D, P) workload description of every kernel, trace the
kernel once with abstract inputs, read the ``pallas_call`` IR -- grid,
BlockSpecs, index-map jaxprs, scratch refs, kernel-body jaxpr -- and derive
the full :class:`~repro.core.kernel_spec.KernelSpec` statically:

  * data parameters D from argument shapes, program parameters P from
    symbolic block sizes (two-trace value matching),
  * per-operand HBM traffic and block residency from index-map dependence
    analysis,
  * VMEM stage footprint from the padded tile products,
  * FLOP counts and MXU share from a jaxpr cost walk,
  * feasibility constraints (caps + sublane/lane granularity) as the same
    Python-syntax strings hand specs use.

Entry points: ``spec_from_kernel(fn, grid_spec, *, hw=V5E)`` for the spec
alone; ``auto_register(fn, grid_spec)`` to wire a kernel into the driver
registry, the artifact cache (keyed by the traced kernel's content hash),
tuned dispatch and telemetry with zero hand-written spec code.  The
GridSpecs mirroring the four hand-written tier-1 specs live in
``repro.introspect.tier1`` (imported on demand; they exist to prove
behavioral equivalence, production tier-1 dispatch keeps the hand specs).
"""

from .derive import spec_from_kernel
from .gridspec import GridSpec, IntrospectError, trace_points
from .registry import AutoKernel, auto_kernels, auto_register, get_auto
from .trace import Capture, OperandCapture, capture_kernel
from .costwalk import BodyCost, body_cost

__all__ = [
    "GridSpec", "IntrospectError", "trace_points",
    "Capture", "OperandCapture", "capture_kernel",
    "BodyCost", "body_cost",
    "spec_from_kernel",
    "AutoKernel", "auto_register", "get_auto", "auto_kernels",
]
