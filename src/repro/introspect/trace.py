"""Pallas kernel capture: trace once, read the IR, never execute.

The TPU analogue of the paper's LLVM pass entry point.  The kernel builder
is traced to a jaxpr with abstract arguments (``jax.make_jaxpr`` over
``ShapeDtypeStruct``s -- no buffers, no compilation), and the single
``pallas_call`` equation is located inside it.  Everything the spec
derivation needs is read straight off that equation:

  * ``grid_mapping.grid``             -- concrete grid extents at the trace,
  * ``grid_mapping.block_mappings``   -- per-operand block shapes plus the
    *index-map jaxprs*, on which a data-flow reachability pass computes
    which grid axes each operand's index map actually uses (the block-
    residency analysis: an operand whose map ignores the fast axes is
    fetched once per outer step),
  * the kernel-body jaxpr's trailing ``MemRef`` invars -- VMEM scratch
    shapes and dtypes,
  * the kernel-body jaxpr itself -- fed to the cost walk (costwalk.py) and
    hashed into the spec's ``source_fingerprint``.
"""

from __future__ import annotations

import functools
import hashlib
from dataclasses import dataclass
from typing import Any, Mapping

import jax
from jax.extend import core as jax_core

from .gridspec import GridSpec, IntrospectError

__all__ = ["OperandCapture", "Capture", "capture_kernel"]

Dims = Mapping[str, int]


@dataclass
class OperandCapture:
    """One pallas_call operand as seen in the traced IR."""

    block_shape: tuple[int, ...]
    dep_axes: tuple[int, ...]        # grid-axis positions the index map uses
    dtype: Any
    is_output: bool = False
    is_scratch: bool = False


@dataclass
class Capture:
    """Everything read off one traced ``pallas_call`` site."""

    grid: tuple[int, ...]
    operands: list[OperandCapture]   # inputs, outputs, then scratch
    body: Any                        # kernel-body jaxpr (for the cost walk)
    fingerprint: str                 # sha256 of the canonical IR description


def _find_pallas_eqns(jaxpr, out=None):
    """All pallas_call equations reachable from a jaxpr (through pjit etc.)."""
    if out is None:
        out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
            continue
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                _find_pallas_eqns(sub, out)
    return out


def _sub_jaxprs(param):
    """Jaxprs nested inside an equation parameter value."""
    if hasattr(param, "jaxpr"):          # ClosedJaxpr
        yield param.jaxpr
    elif hasattr(param, "eqns"):         # raw Jaxpr
        yield param
    elif isinstance(param, (tuple, list)):
        for p in param:
            yield from _sub_jaxprs(p)


def _index_map_axes(closed_jaxpr) -> tuple[int, ...]:
    """Grid-axis positions that influence an index map's outputs.

    Forward data-flow over the index-map jaxpr: each variable carries the
    set of grid-index invars that reach it (conservative union per
    equation, which is exact for the tuple-of-affine-expressions maps
    Pallas kernels use).  Output literals (pinned block coordinates)
    contribute nothing.
    """
    jaxpr = closed_jaxpr.jaxpr
    influence: dict[Any, frozenset[int]] = {
        v: frozenset((i,)) for i, v in enumerate(jaxpr.invars)}
    for eqn in jaxpr.eqns:
        s = frozenset()
        for a in eqn.invars:
            if not isinstance(a, jax_core.Literal):
                s |= influence.get(a, frozenset())
        for o in eqn.outvars:
            influence[o] = s
    used: set[int] = set()
    for o in jaxpr.outvars:
        if not isinstance(o, jax_core.Literal):
            used |= influence.get(o, frozenset())
    return tuple(sorted(used))


def _ref_shape_dtype(aval):
    """(shape, dtype) of a kernel-body MemRef/ShapedArray aval."""
    inner = getattr(aval, "inner_aval", aval)
    return tuple(int(d) for d in inner.shape), inner.dtype


def capture_kernel(fn, grid_spec: GridSpec, D: Dims, P: Dims) -> Capture:
    """Trace ``fn`` at (D, P) and read its single pallas_call site.

    ``fn`` may be jit-wrapped (the underlying function is traced directly,
    so no jit cache entry is created for the synthetic trace shapes).
    """
    inner = getattr(fn, "__wrapped__", fn)
    args = grid_spec.make_args(D)
    kwargs = {**grid_spec.call_kwargs,
              **{p: int(P[p]) for p in grid_spec.program_params}}
    try:
        closed = jax.make_jaxpr(functools.partial(inner, **kwargs))(*args)
    except Exception as e:
        raise IntrospectError(
            f"{grid_spec.name}: tracing the kernel at D={dict(D)} "
            f"P={dict(P)} failed: {type(e).__name__}: {e}") from e
    eqns = _find_pallas_eqns(closed.jaxpr)
    if len(eqns) != 1:
        raise IntrospectError(
            f"{grid_spec.name}: expected exactly one pallas_call in the "
            f"traced kernel, found {len(eqns)} (fused multi-kernel builders "
            f"are not introspectable; see ROADMAP open items)")
    eqn = eqns[0]
    gm = eqn.params["grid_mapping"]
    if getattr(gm, "num_index_operands", 0) or \
            getattr(gm, "num_dynamic_grid_bounds", 0):
        raise IntrospectError(
            f"{grid_spec.name}: scalar-prefetch operands / dynamic grid "
            f"bounds are not statically analyzable (see ROADMAP open items)")
    body = eqn.params["jaxpr"]
    n_io = gm.num_inputs + gm.num_outputs
    body_invars = list(body.invars)
    if len(body_invars) != n_io + gm.num_scratch_operands:
        raise IntrospectError(
            f"{grid_spec.name}: kernel body has {len(body_invars)} refs, "
            f"expected {n_io} operands + {gm.num_scratch_operands} scratch")

    operands: list[OperandCapture] = []
    for i, bm in enumerate(gm.block_mappings):
        _, dtype = _ref_shape_dtype(body_invars[i].aval)
        # Block dims are Pallas ``Blocked(n)`` entries; a ``Squeezed`` dim
        # has no block size and spans one element.
        block = tuple(int(getattr(b, "block_size", 1))
                      for b in bm.block_shape)
        operands.append(OperandCapture(
            block_shape=block,
            dep_axes=_index_map_axes(bm.index_map_jaxpr),
            dtype=dtype,
            is_output=i >= gm.num_inputs,
        ))
    for v in body_invars[n_io:]:
        shape, dtype = _ref_shape_dtype(v.aval)
        operands.append(OperandCapture(
            block_shape=shape, dep_axes=(), dtype=dtype, is_scratch=True))

    canonical = "\n".join([
        f"grid={tuple(int(g) for g in gm.grid)}",
        *(f"operand shape={op.block_shape} deps={op.dep_axes} "
          f"dtype={op.dtype} out={op.is_output} scratch={op.is_scratch}"
          for op in operands),
        str(body),
    ])
    return Capture(
        grid=tuple(int(g) for g in gm.grid),
        operands=operands,
        body=body,
        fingerprint=hashlib.sha256(canonical.encode()).hexdigest()[:16],
    )
