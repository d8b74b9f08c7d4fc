"""Derive a full KernelSpec from two captures of a Pallas kernel.

The symbolic-reconstruction half of the pass.  A single trace yields only
concrete numbers (grid extents, block dims); tracing the kernel at *two*
(D, P) assignments in which every parameter takes a unique pair of values
turns each number back into the symbol that produced it:

  * a block dimension whose values track P[p] across both traces is the
    program parameter ``p``; tracking D[d] makes it the data parameter
    ``d``; a value constant across traces is a literal,
  * a grid extent equal to D[d] in both traces is an unblocked axis; equal
    to ceil(D[d] / P[p]) for exactly one (d, p) pair it is the axis that
    tiles ``d`` with block ``p``,
  * leading literal-1 block dims (Pallas' mapped batch dims) are squeezed,
    preserving the (sublane, lane) trailing pair.

Feasibility constraints are synthesized in the same Python-syntax string
form hand specs use: one ``"p <= d"`` cap per blocked grid axis, plus one
granularity constraint per program parameter -- lane granularity (128) when
the cost walk saw the parameter as a minor-most dimension anywhere in the
body, sublane granularity (8) otherwise.  VMEM capacity is enforced by the
same built-in pipeline-buffer check every spec gets.

The VMEM footprint counts one body intermediate besides the blocks and the
scratch: the largest value that no operand buffer can hold, i.e. whose
(shape, element width) matches no block -- flash attention's (bq, bkv)
scores, the SSD's (chunk, chunk) gate, layernorm's f32 upcast of a bf16
row block.  Mosaic keeps such values in VMEM; without them the footprint
undercounts what the compiler allocates by up to 2.6x (a 2048 x 2048 flash
tile).

FLOPs per grid-domain point come from the cost walk: per-step FLOPs divided
by the product of the blocked program parameters, cross-checked between the
two traces (a mismatch means the FLOP density depends on P itself --
impossible to express in the ``flops_per_point`` model -- and demands an
explicit GridSpec hint).
"""

from __future__ import annotations

import math
from typing import Mapping

from repro.core.device_model import HardwareParams, V5E, dtype_bytes
from repro.core.kernel_spec import GridAxis, KernelSpec, Operand

from .costwalk import body_cost
from .gridspec import GridSpec, IntrospectError, trace_points
from .trace import Capture, capture_kernel

__all__ = ["spec_from_kernel"]

Dims = Mapping[str, int]

# Two traces whose derived per-point FLOPs differ by more than this are
# P-dependent (not expressible as a constant flops_per_point).
_FLOP_TOLERANCE = 0.25


def _match_dim(name: str, what: str, v1: int, v2: int,
               D1: Dims, P1: Dims, D2: Dims, P2: Dims) -> str | int:
    """Symbol (param name) or literal behind a pair of traced values."""
    hits = [p for p in P1 if P1[p] == v1 and P2[p] == v2]
    hits += [d for d in D1 if D1[d] == v1 and D2[d] == v2]
    if len(hits) == 1:
        return hits[0]
    if len(hits) > 1:
        raise IntrospectError(
            f"{name}: {what} value ({v1}, {v2}) matches several parameters "
            f"{hits}; trace points are not discriminating")
    if v1 == v2:
        return int(v1)
    raise IntrospectError(
        f"{name}: {what} value changed between traces ({v1} -> {v2}) but "
        f"matches no data or program parameter")


def _match_grid(gs: GridSpec, cap1: Capture, cap2: Capture,
                D1: Dims, P1: Dims, D2: Dims, P2: Dims
                ) -> tuple[GridAxis, ...]:
    if len(cap1.grid) != len(cap2.grid):
        raise IntrospectError(
            f"{gs.name}: grid rank changed between traces "
            f"({cap1.grid} vs {cap2.grid})")
    axes = []
    for i, (e1, e2) in enumerate(zip(cap1.grid, cap2.grid)):
        nm = f"ax{i}"
        direct = [d for d in D1 if D1[d] == e1 and D2[d] == e2]
        ratio = [(d, p) for d in D1 for p in P1
                 if math.ceil(D1[d] / P1[p]) == e1
                 and math.ceil(D2[d] / P2[p]) == e2]
        if len(direct) + len(ratio) > 1:
            raise IntrospectError(
                f"{gs.name}: grid axis {i} extent ({e1}, {e2}) is ambiguous "
                f"(direct={direct}, ratio={ratio})")
        if direct:
            axes.append(GridAxis(nm, direct[0], None))
        elif ratio:
            axes.append(GridAxis(nm, ratio[0][0], ratio[0][1]))
        elif e1 == e2:
            axes.append(GridAxis(nm, int(e1), None))
        else:
            raise IntrospectError(
                f"{gs.name}: grid axis {i} extent ({e1}, {e2}) matches no "
                f"data extent and no ceil(data/program) division")
    return tuple(axes)


def _squeeze(t1: tuple[int, ...], t2: tuple[int, ...]):
    """Drop leading mapped batch dims (literal 1 in both traces), keeping at
    least the trailing (sublane, lane) pair."""
    while len(t1) > 2 and t1[0] == 1 and t2[0] == 1:
        t1, t2 = t1[1:], t2[1:]
    return t1, t2


def _match_operands(gs: GridSpec, cap1: Capture, cap2: Capture,
                    axes: tuple[GridAxis, ...],
                    D1: Dims, P1: Dims, D2: Dims, P2: Dims
                    ) -> tuple[Operand, ...]:
    n_in = 0
    out = []
    for idx, (op1, op2) in enumerate(zip(cap1.operands, cap2.operands)):
        if (op1.is_output, op1.is_scratch) != (op2.is_output, op2.is_scratch) \
                or op1.dep_axes != op2.dep_axes:
            raise IntrospectError(
                f"{gs.name}: operand {idx} structure changed between traces")
        t1, t2 = _squeeze(op1.block_shape, op2.block_shape)
        if len(t1) != len(t2):
            raise IntrospectError(
                f"{gs.name}: operand {idx} rank changed between traces")
        tile = tuple(
            _match_dim(gs.name, f"operand {idx} dim {j}", v1, v2,
                       D1, P1, D2, P2)
            for j, (v1, v2) in enumerate(zip(t1, t2)))
        if op1.is_scratch:
            nm = f"scratch{idx}"
        elif op1.is_output:
            nm = f"out{idx}"
        else:
            nm = f"in{idx}"
            n_in += 1
        out.append(Operand(
            name=nm,
            tile=tile,
            deps=tuple(axes[a].name for a in op1.dep_axes),
            dtype_bytes=dtype_bytes(op1.dtype),
            is_output=op1.is_output,
            vmem_only=op1.is_scratch,
        ))
    if n_in == 0:
        raise IntrospectError(f"{gs.name}: kernel has no input operands")
    return tuple(out)


def _block_key(shape: tuple[int, ...]) -> tuple[int, ...]:
    """A block shape with its leading mapped 1-dims squeezed (as the body
    sees the loaded block)."""
    while len(shape) > 2 and shape[0] == 1:
        shape = shape[1:]
    return tuple(shape)


def _derive_intermediate(gs: GridSpec, cap1: Capture, cap2: Capture,
                         c1, c2, D1: Dims, P1: Dims, D2: Dims, P2: Dims
                         ) -> Operand | None:
    """The largest body intermediate that no operand buffer can hold, as a
    VMEM-only pseudo-operand, or None.

    "Largest" is judged at the largest candidate tiles, not at the small
    trace points: a (128, chunk) row form outweighs the (chunk, chunk) gate
    at a traced chunk of 112 but not at the chunks the tuner chooses.
    """
    if len(c1.values) != len(c2.values):
        raise IntrospectError(
            f"{gs.name}: kernel body structure changed between traces")
    blocks1 = {(_block_key(op.block_shape), dtype_bytes(op.dtype))
               for op in cap1.operands}
    blocks2 = {(_block_key(op.block_shape), dtype_bytes(op.dtype))
               for op in cap2.operands}
    p_max = {p: max(gs.param_candidates.get(p, (2048,)))
             for p in gs.program_params}
    best = None
    for (s1, itemsize), (s2, _) in zip(c1.values, c2.values):
        if ((s1, itemsize) in blocks1 or (s2, itemsize) in blocks2
                or len(s1) != len(s2)):
            continue
        try:
            tile = tuple(
                _match_dim(gs.name, f"intermediate dim {j}", v1, v2,
                           D1, P1, D2, P2)
                for j, (v1, v2) in enumerate(zip(s1, s2)))
        except IntrospectError:
            continue
        size = itemsize * math.prod(
            p_max.get(t, D2.get(t)) if isinstance(t, str) else t
            for t in tile)
        if best is None or size > best[0]:
            best = (size, tile, itemsize)
    if best is None:
        return None
    return Operand(name="interm", tile=best[1], deps=(),
                   dtype_bytes=best[2], vmem_only=True)


def _derive_flops(gs: GridSpec, c1, c2,
                  axes: tuple[GridAxis, ...],
                  P1: Dims, P2: Dims) -> tuple[float, float]:
    """(flops_per_point, mxu_fraction) from the cost walk, or the hints."""
    mxu = gs.mxu_fraction
    if mxu is None:
        mxu = c1.mxu_fraction_estimate
    if gs.flops_per_point is not None:
        return float(gs.flops_per_point), float(mxu)
    blocked1 = math.prod(P1[a.block] for a in axes if a.block) or 1
    blocked2 = math.prod(P2[a.block] for a in axes if a.block) or 1
    step1 = c1.dot_flops if c1.dot_flops else c1.vpu_flops
    step2 = c2.dot_flops if c2.dot_flops else c2.vpu_flops
    if step1 <= 0 or step2 <= 0:
        raise IntrospectError(
            f"{gs.name}: cost walk found no countable FLOPs; pass "
            f"flops_per_point in the GridSpec")
    f1, f2 = step1 / blocked1, step2 / blocked2
    rel = abs(f1 - f2) / max(f1, f2)
    if rel > _FLOP_TOLERANCE:
        raise IntrospectError(
            f"{gs.name}: per-point FLOPs differ between traces "
            f"({f1:.1f} vs {f2:.1f}): the FLOP density depends on the "
            f"program parameters; pass flops_per_point in the GridSpec")
    if f1 == f2:
        return float(f1) * gs.flop_scale, float(mxu)
    mean = (f1 + f2) / 2.0
    # Round to two significant digits: the residual spread between traces
    # comes from amortized per-step terms (1/P), which the fitted overhead
    # metric absorbs anyway.
    digits = 1 - int(math.floor(math.log10(abs(mean))))
    return round(mean, digits) * gs.flop_scale, float(mxu)


def _derive_constraints(gs: GridSpec, axes: tuple[GridAxis, ...],
                        cap1: Capture, cap2: Capture, c1, c2,
                        P1: Dims, P2: Dims) -> tuple[str, ...]:
    cons: list[str] = []
    for a in axes:
        if a.block is not None and isinstance(a.data, str):
            cons.append(f"{a.block} <= {a.data}")
    lane1 = set(c1.minor_dims)
    lane2 = set(c2.minor_dims)
    for op1, op2 in zip(cap1.operands, cap2.operands):
        lane1.add(int(op1.block_shape[-1]))
        lane2.add(int(op2.block_shape[-1]))
    for p in gs.program_params:
        grain = 128 if (P1[p] in lane1 and P2[p] in lane2) else 8
        cons.append(f"{p} % {grain} == 0")
    return tuple(cons) + tuple(gs.extra_constraints)


def spec_from_kernel(fn, grid_spec: GridSpec, *,
                     hw: HardwareParams = V5E) -> KernelSpec:
    """Statically derive a full KernelSpec from a Pallas kernel builder.

    ``fn`` is the kernel's (possibly jit-wrapped) builder; ``grid_spec``
    declares its tunable interface and optional tuning policy.  The kernel
    is traced twice at synthetic (D, P) points -- nothing executes -- and
    grid, operands (with block-residency dependences), VMEM footprint,
    FLOPs, and feasibility constraints are reconstructed from the IR.  The
    result is a drop-in peer of a hand-written spec: it feeds the same
    collect -> fit -> choose -> plan pipeline, and its
    ``source_fingerprint`` (a hash of the traced IR) rides into the
    driver-artifact cache key so editing the kernel body invalidates its
    tuning artifacts.

    ``hw`` is the target device profile; it scopes nothing at derive time
    (granularities on TPU are fixed at 8 x 128) but is threaded through for
    API symmetry with the rest of the pipeline.
    """
    from repro.trace import trace_span

    with trace_span("spec_from_kernel", kernel=grid_spec.name) as sp:
        (D1, P1), (D2, P2) = trace_points(grid_spec)
        cap1 = capture_kernel(fn, grid_spec, D1, P1)
        cap2 = capture_kernel(fn, grid_spec, D2, P2)
        axes = _match_grid(grid_spec, cap1, cap2, D1, P1, D2, P2)
        operands = _match_operands(grid_spec, cap1, cap2, axes,
                                   D1, P1, D2, P2)
        # One cost walk per capture, shared by the FLOP and constraint
        # passes.
        cost1, cost2 = body_cost(cap1.body), body_cost(cap2.body)
        interm = _derive_intermediate(grid_spec, cap1, cap2, cost1, cost2,
                                      D1, P1, D2, P2)
        if interm is not None:
            operands = operands + (interm,)
        flops, mxu = _derive_flops(grid_spec, cost1, cost2, axes, P1, P2)
        constraints = _derive_constraints(grid_spec, axes, cap1, cap2,
                                          cost1, cost2, P1, P2)
        spec = KernelSpec(
            name=grid_spec.name,
            data_params=tuple(grid_spec.data_params),
            program_params=tuple(grid_spec.program_params),
            grid=axes,
            operands=operands,
            flops_per_point=flops,
            constraints=constraints,
            mxu_fraction=mxu,
            param_candidates=dict(grid_spec.param_candidates),
            pipeline_buffers=grid_spec.pipeline_buffers,
            fit_vars=dict(grid_spec.fit_vars),
            probe_hints=dict(grid_spec.probe_hints),
            source_fingerprint=cap1.fingerprint,
        )
        # Self-check: the symbolic grid must reproduce both traced grids
        # exactly.
        for D, P, cap in ((D1, P1, cap1), (D2, P2, cap2)):
            got = spec.grid_extents(D, P)
            if got != cap.grid:
                raise IntrospectError(
                    f"{grid_spec.name}: derived grid {got} does not "
                    f"reproduce the traced grid {cap.grid} at D={dict(D)} "
                    f"P={dict(P)}")
        sp.set(fingerprint=cap1.fingerprint, n_operands=len(operands))
    return spec
