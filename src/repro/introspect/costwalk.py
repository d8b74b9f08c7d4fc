"""Jaxpr cost walk: per-grid-step FLOP counts and lane-dimension analysis.

Walks the captured kernel-body jaxpr (trace.py) and accumulates:

  * ``dot_flops``  -- 2 * prod(output shape) * prod(contracting dims) per
    ``dot_general`` (the MXU work of one grid step),
  * ``vpu_flops``  -- one FLOP per output element of every arithmetic /
    transcendental / reduction primitive (the VPU work),
  * ``minor_dims`` -- the set of minor-most (lane) dimension sizes of every
    array value in the body, operands and intermediates alike,
  * ``values``     -- (shape, itemsize) of every rank >= 2 value the body
    computes, in walk order (the VMEM-intermediate candidates).

Conditional sub-jaxprs (``pl.when`` -> ``cond``) with no ``dot_general``
are *excluded* from the FLOP counts: they are pipeline-boundary work
(accumulator init, final store) amortized over the whole reduction chain,
not steady-state per-step work.  A conditional that holds MXU work is the
step's own work behind a block predicate (flash attention's skip of masked
blocks): its costliest branch counts as one step's, and how many steps
run it is the spec's FLOP policy.  Every branch still contributes to
``minor_dims`` -- a dimension that must be lane-aligned is lane-aligned no
matter how often the code runs.

``minor_dims`` drives the alignment-constraint derivation: a program
parameter whose traced value appears as the minor-most axis of any value
needs lane granularity (128); every other program parameter needs sublane
granularity (8).  This is how the analysis discovers, e.g., that flash
attention's kv tile is lane-critical (the (bq, bkv) score matrix) even
though bkv is never the minor axis of any *operand* tile.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

from jax.extend import core as jax_core

__all__ = ["BodyCost", "body_cost"]

# Primitives counted as one FLOP per output element on the VPU.
_ELEMENTWISE = {
    "add", "sub", "mul", "div", "rem", "max", "min", "pow", "integer_pow",
    "exp", "exp2", "log", "log1p", "expm1", "tanh", "logistic", "erf",
    "rsqrt", "sqrt", "abs", "neg", "sign", "floor", "ceil", "round",
    "select_n", "clamp", "nextafter", "atan2", "sin", "cos",
}
# Reductions / scans: one FLOP per *input* element.
_REDUCTIONS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
    "argmax", "argmin",
}


@dataclass
class BodyCost:
    dot_flops: float = 0.0
    vpu_flops: float = 0.0
    minor_dims: set = field(default_factory=set)
    values: list = field(default_factory=list)

    @property
    def total_flops(self) -> float:
        return self.dot_flops + self.vpu_flops

    @property
    def mxu_fraction_estimate(self) -> float:
        """Crude MXU share: 1.0 for dot-dominated bodies, 0.0 for pure VPU."""
        return 1.0 if self.dot_flops > 0 else 0.0


def _shape(atom) -> tuple[int, ...]:
    aval = getattr(atom, "aval", None)
    inner = getattr(aval, "inner_aval", aval)
    shape = getattr(inner, "shape", ())
    try:
        return tuple(int(d) for d in shape)
    except TypeError:
        return ()


def _itemsize(atom) -> int:
    aval = getattr(atom, "aval", None)
    inner = getattr(aval, "inner_aval", aval)
    return int(inner.dtype.itemsize)


def _note_minor(cost: BodyCost, atom) -> None:
    # Only rank >= 2 values occupy a (sublane, lane) layout; a rank-1
    # reduction output lives across sublanes, so its single dimension says
    # nothing about lane alignment.
    shape = _shape(atom)
    if len(shape) >= 2:
        cost.minor_dims.add(int(shape[-1]))


def _dot_flops(eqn) -> float:
    (lhs_c, _), _ = eqn.params["dimension_numbers"]
    lhs_shape = _shape(eqn.invars[0])
    contract = math.prod(lhs_shape[int(a)] for a in lhs_c) if lhs_c else 1
    out = math.prod(_shape(eqn.outvars[0])) or 1
    return 2.0 * out * contract


def _walk(jaxpr, cost: BodyCost, count_flops: bool) -> None:
    for eqn in jaxpr.eqns:
        for atom in list(eqn.invars) + list(eqn.outvars):
            if not isinstance(atom, jax_core.Literal):
                _note_minor(cost, atom)
        for atom in eqn.outvars:
            shape = _shape(atom)
            if len(shape) >= 2:
                cost.values.append((shape, _itemsize(atom)))
        name = eqn.primitive.name
        if name == "dot_general":
            if count_flops:
                cost.dot_flops += _dot_flops(eqn)
        elif name in _ELEMENTWISE:
            if count_flops:
                cost.vpu_flops += math.prod(_shape(eqn.outvars[0])) or 1
        elif name in _REDUCTIONS:
            if count_flops:
                cost.vpu_flops += math.prod(_shape(eqn.invars[0])) or 1
        if name == "cond":
            _walk_cond(eqn, cost, count_flops)
            continue
        # Recurse into nested jaxprs.  Loops keep contributing lane
        # dimensions but not steady-state FLOPs.
        sub_count = count_flops and name != "while"
        for v in eqn.params.values():
            for sub in _sub(v):
                _walk(sub, cost, sub_count)


def _walk_cond(eqn, cost: BodyCost, count_flops: bool) -> None:
    """Walk every branch; count the costliest one's FLOPs if any branch
    holds MXU work, else none (pipeline-boundary work)."""
    branches = []
    for sub in _sub(eqn.params["branches"]):
        branch = BodyCost()
        _walk(sub, branch, count_flops)
        cost.minor_dims |= branch.minor_dims
        cost.values.extend(branch.values)
        branches.append(branch)
    if count_flops and any(b.dot_flops for b in branches):
        top = max(branches, key=lambda b: (b.dot_flops, b.vpu_flops))
        cost.dot_flops += top.dot_flops
        cost.vpu_flops += top.vpu_flops


def _sub(param):
    if hasattr(param, "jaxpr"):
        yield param.jaxpr
    elif hasattr(param, "eqns"):
        yield param
    elif isinstance(param, (tuple, list)):
        for p in param:
            yield from _sub(p)


def body_cost(body_jaxpr) -> BodyCost:
    """Cost summary of one kernel-body jaxpr (one grid step's work)."""
    cost = BodyCost()
    jaxpr = getattr(body_jaxpr, "jaxpr", body_jaxpr)
    for v in jaxpr.invars:
        _note_minor(cost, v)
    _walk(jaxpr, cost, count_flops=True)
    return cost
