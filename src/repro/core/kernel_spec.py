"""Kernel specifications: the (D, P) interface of a tunable kernel.

A KernelSpec is the TPU analogue of the paper's annotated CUDA kernel
(Section V-A): it names the data parameters D, the program parameters P
(Pallas BlockSpec tile sizes instead of thread-block dims), and carries the
*constraint strings in Python syntax* that the paper has users write into
configuration files (e.g. "bx < by**2, bx < N" -> here e.g.
"bm * bk * 2 <= vmem").

From the spec we derive, fully analytically:
  * the grid (lexicographic, last axis fastest -- Pallas/Mosaic semantics),
  * per-operand HBM traffic including *block residency*: an operand whose
    index map does not depend on the fastest-varying grid axes is kept in
    VMEM across consecutive steps; the fetch count is the product of the
    extents of all axes up to the fastest axis the operand depends on,
  * the VMEM stage footprint (padded to sublane x lane granularity),
  * symbolic Expr versions of grid-steps and stage-bytes for the rational
    program skeleton (core/perf_model.py).

The same description feeds (a) the ground-truth simulator and (b) the
feasible-set enumerator of the runtime driver.  The *fitted* quantities
(effective per-step memory/compute/overhead times) are never derived from
here -- they come from probing the device oracle.
"""

from __future__ import annotations

import ast
import functools
import math
from dataclasses import dataclass, field
from typing import Iterator, Mapping, Sequence

import numpy as np

from .device_model import (HardwareParams, KernelTraffic, TrafficOperand,
                           TrafficTable, V5E)
from .rational_program import Ceil, Const, Expr, Floor, Max, Min, ceil_div, var

__all__ = [
    "Operand", "GridAxis", "KernelSpec", "CandidateTable", "SpecError",
    "matmul_spec", "flash_attention_spec", "moe_gmm_spec", "ssd_scan_spec",
    "POLYBENCH_SUITE", "polybench_suite",
]

Dims = Mapping[str, int]


class SpecError(ValueError):
    """A kernel-spec constraint string is malformed or references a symbol
    that is neither a data parameter, a program parameter, nor one of the
    evaluation built-ins (``vmem``, ``math``, ``np``).

    Constraint strings are user input (the paper's Section V-A configuration
    files); a typo'd symbol used to surface as a bare ``NameError`` swallowed
    into an all-infeasible mask.  Now it is diagnosed by name, eagerly, the
    first time the constraint is evaluated.
    """


@functools.lru_cache(maxsize=4096)
def _constraint_names(constraint: str) -> frozenset[str]:
    """Bare symbols referenced by a constraint expression (cached: the AST
    parse would otherwise re-run for every feasible_mask call of every
    collect/search loop).  Only ``Name`` loads count, so ``math.ceil``
    checks ``math``, not ``ceil``.  Raises SpecError on syntax errors."""
    try:
        tree = ast.parse(constraint, mode="eval")
    except SyntaxError as e:
        raise SpecError(
            f"constraint {constraint!r} is not a valid Python expression: "
            f"{e.msg}") from e
    return frozenset(n.id for n in ast.walk(tree)
                     if isinstance(n, ast.Name))


def _check_constraint_symbols(constraint: str, known: set[str],
                              spec_name: str) -> None:
    """Raise SpecError naming the offending symbol(s) of a constraint."""
    try:
        names = _constraint_names(constraint)
    except SpecError as e:
        raise SpecError(f"spec {spec_name!r}: {e}") from None
    unknown = sorted(names - known)
    if unknown:
        raise SpecError(
            f"constraint {constraint!r} of spec {spec_name!r} references "
            f"unknown symbol(s) {', '.join(map(repr, unknown))}; known "
            f"symbols are the data/program parameters "
            f"{sorted(known - {'vmem', 'math', 'np'})} plus 'vmem', "
            f"'math' and 'np'")


def _pad(x, m):
    """Round up to a multiple of m (works elementwise on ndarrays)."""
    return ((x + m - 1) // m) * m


@dataclass
class CandidateTable:
    """Struct-of-arrays feasible configuration set: one column per program
    parameter.

    This is the columnar contract of the whole pipeline: the enumerator
    produces it, the device oracles consume it through ``traffic_table``,
    and the generated drivers evaluate the rational program over it in one
    ndarray pass (no per-config Python loop anywhere).
    """

    params: tuple[str, ...]
    columns: dict[str, np.ndarray]      # each (n,) int64

    def __post_init__(self) -> None:
        self.columns = {p: np.asarray(c, dtype=np.int64)
                        for p, c in self.columns.items()}

    def __len__(self) -> int:
        if not self.params:
            return 0
        return int(self.columns[self.params[0]].shape[0])

    def __bool__(self) -> bool:
        return len(self) > 0

    def __getitem__(self, param: str) -> np.ndarray:
        return self.columns[param]

    def row(self, i: int) -> dict[str, int]:
        return {p: int(self.columns[p][i]) for p in self.params}

    def rows(self) -> Iterator[dict[str, int]]:
        for i in range(len(self)):
            yield self.row(i)

    def select(self, index) -> "CandidateTable":
        """New table keeping rows selected by a boolean mask or index array."""
        return CandidateTable(
            self.params, {p: c[index] for p, c in self.columns.items()})

    @classmethod
    def from_rows(cls, params: Sequence[str],
                  rows: Sequence[Mapping[str, int]]) -> "CandidateTable":
        params = tuple(params)
        return cls(params, {
            p: np.array([r[p] for r in rows], dtype=np.int64) for p in params})

    @classmethod
    def product(cls, params: Sequence[str],
                axes: Sequence[Sequence[int]]) -> "CandidateTable":
        """Full Cartesian grid over per-parameter candidate values."""
        params = tuple(params)
        if not params:
            return cls(params, {})
        grids = np.meshgrid(*[np.asarray(a, dtype=np.int64) for a in axes],
                            indexing="ij")
        return cls(params, {p: g.reshape(-1)
                            for p, g in zip(params, grids)})


@dataclass(frozen=True)
class GridAxis:
    """One grid dimension: extent = ceil(D[data] / P[block]) (or a literal)."""

    name: str
    data: str | int              # data param name or literal extent
    block: str | None = None     # program param name (None => extent = data)

    def extent(self, D: Dims, P: Dims) -> int:
        total = D[self.data] if isinstance(self.data, str) else self.data
        if self.block is None:
            return int(total)
        return math.ceil(total / P[self.block])

    def extent_expr(self) -> Expr:
        total = var(self.data) if isinstance(self.data, str) else Const(self.data)
        if self.block is None:
            return total
        return ceil_div(total, var(self.block))


@dataclass(frozen=True)
class Operand:
    """One kernel operand with its tile template and grid dependencies.

    ``tile``: each entry is a program-param name, data-param name, or literal.
    ``deps``: grid axis names the BlockSpec index_map depends on.
    ``vmem_only``: a VMEM scratch buffer or body intermediate -- it counts
    toward the stage footprint but moves no HBM bytes.
    """

    name: str
    tile: tuple[str | int, ...]
    deps: tuple[str, ...]
    dtype_bytes: int = 2
    is_output: bool = False
    vmem_only: bool = False

    def tile_shape(self, D: Dims, P: Dims) -> tuple[int, ...]:
        out = []
        for t in self.tile:
            if isinstance(t, str):
                out.append(P[t] if t in P else D[t])
            else:
                out.append(int(t))
        return tuple(out)


@dataclass
class KernelSpec:
    name: str
    data_params: tuple[str, ...]
    program_params: tuple[str, ...]
    grid: tuple[GridAxis, ...]
    operands: tuple[Operand, ...]
    flops_per_point: float                  # FLOPs per grid-domain point
    # FLOP domain: product over these axes of (data extents) -- defaults to
    # product of all grid axes' *data* extents.
    constraints: tuple[str, ...] = ()       # python-syntax strings over D u P
    mxu_fraction: float = 1.0
    # candidate values per program param (powers of two by default)
    param_candidates: dict[str, tuple[int, ...]] = field(default_factory=dict)
    pipeline_buffers: int = 2               # double buffering by default
    # which variables each fitted low-level metric depends on (keeps the
    # Vandermonde system small -- paper: "degree bounds ... relatively small")
    fit_vars: dict[str, tuple[str, ...]] = field(default_factory=dict)
    # per-data-param probe values overriding the default small-size sweep of
    # collect.default_probe_data -- count-like params (experts, batch*heads)
    # declare small fixed values here so new kernels need no edits to core
    probe_hints: dict[str, tuple[int, ...]] = field(default_factory=dict)
    # Content identity of the traced kernel an introspected spec was derived
    # from (repro/introspect): folded into the driver-artifact cache key, so
    # editing the kernel body invalidates its tuning artifacts by
    # construction.  Empty for hand-written specs.
    source_fingerprint: str = ""

    # -- derived, analytic ----------------------------------------------------
    def grid_extents(self, D: Dims, P: Dims) -> tuple[int, ...]:
        return tuple(a.extent(D, P) for a in self.grid)

    def grid_steps(self, D: Dims, P: Dims) -> int:
        n = 1
        for e in self.grid_extents(D, P):
            n *= e
        return n

    def grid_steps_expr(self) -> Expr:
        e: Expr = Const(1.0)
        for a in self.grid:
            e = e * a.extent_expr()
        return e

    def flops_total(self, D: Dims, P: Dims) -> float:
        n = 1.0
        for a in self.grid:
            n *= D[a.data] if isinstance(a.data, str) else a.data
        return self.flops_per_point * n

    def _fetches(self, op: Operand, extents: tuple[int, ...]) -> int:
        """Fetch count under lexicographic grid order, last axis fastest."""
        if op.vmem_only:
            return 0
        names = [a.name for a in self.grid]
        dep_pos = [names.index(d) for d in op.deps if d in names]
        if not dep_pos:
            return 1
        last = max(dep_pos)
        n = 1
        for e in extents[: last + 1]:
            n *= e
        return n

    def vmem_stage_bytes(self, D: Dims, P: Dims,
                         hw: HardwareParams = V5E) -> int:
        total = 0
        for op in self.operands:
            shape = op.tile_shape(D, P)
            dims = list(shape)
            dims[-1] = _pad(dims[-1], hw.lanes)
            if len(dims) >= 2:
                dims[-2] = _pad(dims[-2], hw.sublanes(op.dtype_bytes))
            n = 1
            for d in dims:
                n *= d
            total += n * op.dtype_bytes
        return total

    def vmem_stage_expr(self, hw: HardwareParams = V5E) -> Expr:
        total: Expr = Const(0.0)
        for op in self.operands:
            prod: Expr = Const(float(op.dtype_bytes))
            tile = list(op.tile)
            for i, t in enumerate(tile):
                d: Expr = var(t) if isinstance(t, str) else Const(float(t))
                if i == len(tile) - 1:
                    d = Ceil(d / Const(float(hw.lanes))) * Const(float(hw.lanes))
                elif i == len(tile) - 2:
                    sl = float(hw.sublanes(op.dtype_bytes))
                    d = Ceil(d / Const(sl)) * Const(sl)
                prod = prod * d
            total = total + prod
        return total

    def traffic(self, D: Dims, P: Dims,
                hw: HardwareParams = V5E) -> KernelTraffic:
        extents = self.grid_extents(D, P)
        tiles_in, tiles_out = [], []
        for op in self.operands:
            rec = (op.tile_shape(D, P), self._fetches(op, extents),
                   op.dtype_bytes)
            (tiles_out if op.is_output else tiles_in).append(rec)
        return KernelTraffic(
            grid_steps=self.grid_steps(D, P),
            flops_total=self.flops_total(D, P),
            tiles_in=tiles_in,
            tiles_out=tiles_out,
            vmem_stage_bytes=self.vmem_stage_bytes(D, P, hw),
            mxu_fraction=self.mxu_fraction,
        )

    # -- batched derivations over a CandidateTable ----------------------------
    def grid_extents_batch(self, D: Dims,
                           table: CandidateTable) -> list[np.ndarray]:
        """Per-axis grid extents, each (n,) int64 over the candidate table."""
        n = len(table)
        out = []
        for a in self.grid:
            total = D[a.data] if isinstance(a.data, str) else a.data
            if a.block is None:
                out.append(np.full(n, int(total), dtype=np.int64))
            else:
                out.append(-(-int(total) // table[a.block]))
        return out

    def grid_steps_batch(self, D: Dims, table: CandidateTable) -> np.ndarray:
        steps = np.ones(len(table), dtype=np.int64)
        for e in self.grid_extents_batch(D, table):
            steps = steps * e
        return steps

    def _tile_columns(self, op: Operand, D: Dims,
                      table: CandidateTable) -> np.ndarray:
        """(n, ndim) tile shapes for one operand over the candidate table."""
        n = len(table)
        cols = []
        for t in op.tile:
            if isinstance(t, str) and t in table.columns:
                cols.append(table[t])
            else:
                v = D[t] if isinstance(t, str) else int(t)
                cols.append(np.full(n, int(v), dtype=np.int64))
        return np.stack(cols, axis=1)

    def vmem_stage_bytes_batch(self, D: Dims, table: CandidateTable,
                               hw: HardwareParams = V5E) -> np.ndarray:
        total = np.zeros(len(table), dtype=np.int64)
        for op in self.operands:
            dims = self._tile_columns(op, D, table).copy()
            dims[:, -1] = _pad(dims[:, -1], hw.lanes)
            if dims.shape[1] >= 2:
                dims[:, -2] = _pad(dims[:, -2], hw.sublanes(op.dtype_bytes))
            total = total + np.prod(dims, axis=1) * op.dtype_bytes
        return total

    def traffic_table(self, D: Dims, table: CandidateTable,
                      hw: HardwareParams = V5E) -> TrafficTable:
        """Columnar ``KernelTraffic`` over every config in ``table``."""
        extents = self.grid_extents_batch(D, table)
        names = [a.name for a in self.grid]
        n = len(table)
        operands = []
        for op in self.operands:
            dep_pos = [names.index(d) for d in op.deps if d in names]
            if op.vmem_only:
                fetches = np.zeros(n, dtype=np.int64)
            elif not dep_pos:
                fetches = np.ones(n, dtype=np.int64)
            else:
                fetches = np.ones(n, dtype=np.int64)
                for e in extents[: max(dep_pos) + 1]:
                    fetches = fetches * e
            operands.append(TrafficOperand(
                name=op.name,
                shapes=self._tile_columns(op, D, table),
                fetches=fetches,
                dtype_bytes=op.dtype_bytes,
                is_output=op.is_output,
            ))
        steps = np.ones(n, dtype=np.int64)
        for e in extents:
            steps = steps * e
        flops = 1.0
        for a in self.grid:
            flops *= D[a.data] if isinstance(a.data, str) else a.data
        return TrafficTable(
            grid_steps=steps,
            flops_total=np.full(n, self.flops_per_point * flops),
            operands=operands,
            vmem_stage_bytes=self.vmem_stage_bytes_batch(D, table, hw),
            mxu_fraction=self.mxu_fraction,
        )

    # -- feasibility / enumeration (Section IV step 4) -------------------------
    def feasible(self, D: Dims, P: Dims, hw: HardwareParams = V5E) -> bool:
        """Scalar feasibility check for a single (D, P) point."""
        table = CandidateTable.from_rows(self.program_params, [P])
        return bool(self.feasible_mask(D, table, hw)[0])

    def feasible_mask(self, D: Dims, table: CandidateTable,
                      hw: HardwareParams = V5E) -> np.ndarray:
        """Vectorized constraint evaluation: (n,) bool over the table.

        The user-written Python-syntax constraint strings (Section V-A) are
        evaluated once with ndarray columns bound to the program parameters;
        a constraint that resists array evaluation falls back to per-row
        scalar evaluation for just that constraint.  Evaluation happens in a
        restricted namespace (no builtins; only the spec's parameters plus
        ``vmem``, ``math`` and ``np``), and a constraint referencing any
        other symbol raises :class:`SpecError` naming it instead of
        silently masking every configuration infeasible.
        """
        n = len(table)
        mask = np.ones(n, dtype=bool)
        env: dict[str, object] = {k: int(v) for k, v in D.items()}
        env.update(table.columns)
        env["vmem"] = hw.vmem_limit_bytes
        known = set(env) | {"math", "np"}
        globs = {"__builtins__": {}, "math": math, "np": np}
        for c in self.constraints:
            _check_constraint_symbols(c, known, self.name)
            try:
                res = eval(c, globs, dict(env))
                mask &= np.broadcast_to(np.asarray(res, dtype=bool), (n,))
            except Exception:
                ok = np.zeros(n, dtype=bool)
                for i in range(n):
                    row = {**{k: int(v) for k, v in D.items()},
                           **table.row(i), "vmem": hw.vmem_limit_bytes}
                    try:
                        ok[i] = bool(eval(c, globs, row))
                    except Exception:
                        ok[i] = False
                mask &= ok
        # Built-in constraint: pipeline_buffers stage buffers must fit the
        # kernel's scoped VMEM budget -- the limit the kernel hands Mosaic
        # (the TPU occupancy analogue of registers/shared-memory limits).
        stage = self.vmem_stage_bytes_batch(D, table, hw)
        mask &= stage * self.pipeline_buffers <= hw.vmem_limit_bytes
        # Tiles may not exceed their data extents beyond one padded block.
        for a in self.grid:
            if a.block is not None and isinstance(a.data, str):
                mask &= table[a.block] <= _pad(int(D[a.data]), 8)
        return mask

    def default_candidates(self, param: str, D: Dims) -> tuple[int, ...]:
        if param in self.param_candidates:
            return self.param_candidates[param]
        # Powers of two, 8 .. 2048: sublane granularity up to a large tile.
        return tuple(2 ** i for i in range(3, 12))

    def candidates(self, D: Dims, hw: HardwareParams = V5E) -> CandidateTable:
        """Columnar feasible configuration table at data size D.

        Enumerates the Cartesian candidate grid as ndarray columns and
        applies every constraint as a vectorized mask.  Which rows actually
        get probed is a repro.search strategy decision (the old even-stride
        ``limit`` head-cut is gone -- it bypassed the strategy/budget
        cache-key identity).
        """
        axes = [self.default_candidates(p, D) for p in self.program_params]
        table = CandidateTable.product(self.program_params, axes)
        return table.select(self.feasible_mask(D, table, hw))

    def metric_fit_vars(self, metric: str) -> tuple[str, ...]:
        if metric in self.fit_vars:
            return self.fit_vars[metric]
        return tuple(self.program_params)


# ---------------------------------------------------------------------------
# Concrete specs for the Pallas kernels in src/repro/kernels/
# ---------------------------------------------------------------------------

def matmul_spec(dtype_bytes: int = 2) -> KernelSpec:
    """C[m,n] = A[m,k] @ B[k,n], grid (i, j, l) with l (the k loop) fastest."""
    return KernelSpec(
        name=f"matmul_b{dtype_bytes * 8}",
        data_params=("m", "n", "k"),
        program_params=("bm", "bn", "bk"),
        grid=(GridAxis("i", "m", "bm"), GridAxis("j", "n", "bn"),
              GridAxis("l", "k", "bk")),
        operands=(
            Operand("lhs", ("bm", "bk"), ("i", "l"), dtype_bytes),
            Operand("rhs", ("bk", "bn"), ("l", "j"), dtype_bytes),
            Operand("out", ("bm", "bn"), ("i", "j"), dtype_bytes,
                    is_output=True),
            # f32 accumulator scratch lives in VMEM but moves no HBM bytes;
            # accounted in stage bytes via a 4-byte VMEM-only pseudo-operand.
            Operand("acc", ("bm", "bn"), (), 4, vmem_only=True),
        ),
        flops_per_point=2.0,  # over the (m, n, k) domain: one FMA per point
        constraints=(
            "bm <= 8 * m", "bn <= 8 * n", "bk <= 8 * k",
            "bm % 8 == 0", "bn % 128 == 0", "bk % 128 == 0",
        ),
        mxu_fraction=1.0,
        param_candidates={
            "bm": (8, 16, 32, 64, 128, 256, 512, 1024),
            "bn": (128, 256, 512, 1024, 2048),
            "bk": (128, 256, 512, 1024, 2048),
        },
        fit_vars={
            "mem_step": ("bm", "bn", "bk"),
            "cmp_step": ("bm", "bn", "bk"),
            "ovh_step": ("bm", "bn", "bk"),
        },
    )


def flash_attention_spec(head_dim: int = 128, causal: bool = True,
                         dtype_bytes: int = 2) -> KernelSpec:
    """Flash attention forward: grid (bh, iq, ikv), kv fastest (online softmax).

    D: bh = batch*heads (flattened), sq, skv.  P: bq, bkv.
    FLOPs per (bh, sq, skv) point: 4*head_dim (QK^T and PV) [*0.5 if causal].
    """
    f = 4.0 * head_dim * (0.5 if causal else 1.0)
    return KernelSpec(
        name=f"flash_attn_d{head_dim}" + ("_causal" if causal else ""),
        data_params=("bh", "sq", "skv"),
        program_params=("bq", "bkv"),
        grid=(GridAxis("b", "bh", None), GridAxis("iq", "sq", "bq"),
              GridAxis("ikv", "skv", "bkv")),
        operands=(
            Operand("q", ("bq", head_dim), ("b", "iq"), dtype_bytes),
            Operand("k", ("bkv", head_dim), ("b", "ikv"), dtype_bytes),
            Operand("v", ("bkv", head_dim), ("b", "ikv"), dtype_bytes),
            Operand("out", ("bq", head_dim), ("b", "iq"), dtype_bytes,
                    is_output=True),
            # VMEM scratch, in kernel declaration order (no HBM traffic):
            Operand("rowmax", ("bq", 128), (), 4, vmem_only=True),  # max m
            Operand("rowsum", ("bq", 128), (), 4, vmem_only=True),  # sum l
            Operand("acc", ("bq", head_dim), (), 4, vmem_only=True),
            # f32 (bq, bkv) score / probability intermediates, which Mosaic
            # keeps in VMEM too (26.5 MiB at bq = bkv = 2048, d = 128).
            Operand("scores", ("bq", "bkv"), (), 4, vmem_only=True),
        ),
        flops_per_point=f,
        constraints=("bq <= sq", "bkv <= skv",
                     "bq % 8 == 0", "bkv % 128 == 0"),
        mxu_fraction=0.85,
        param_candidates={
            "bq": (128, 256, 512, 1024, 2048),
            "bkv": (128, 256, 512, 1024, 2048),
        },
        fit_vars={
            "mem_step": ("bq", "bkv"),
            "cmp_step": ("bq", "bkv"),
            "ovh_step": ("bq", "bkv"),
        },
        probe_hints={"bh": (2, 8)},
    )


def moe_gmm_spec(dtype_bytes: int = 2) -> KernelSpec:
    """Grouped (expert) matmul: E groups of [g, k] @ [k, n].

    D: e (experts resident), g (tokens per expert), k, n.  P: bg, bn, bk.
    Grid (expert, i, j, l), l fastest; expert weights re-fetched per expert.
    """
    return KernelSpec(
        name=f"moe_gmm_b{dtype_bytes * 8}",
        data_params=("e", "g", "k", "n"),
        program_params=("bg", "bn", "bk"),
        grid=(GridAxis("ex", "e", None), GridAxis("i", "g", "bg"),
              GridAxis("j", "n", "bn"), GridAxis("l", "k", "bk")),
        operands=(
            Operand("tokens", ("bg", "bk"), ("ex", "i", "l"), dtype_bytes),
            Operand("weights", ("bk", "bn"), ("ex", "l", "j"), dtype_bytes),
            Operand("out", ("bg", "bn"), ("ex", "i", "j"), dtype_bytes,
                    is_output=True),
            Operand("acc", ("bg", "bn"), (), 4, vmem_only=True),
        ),
        flops_per_point=2.0,
        constraints=("bg <= 8 * g", "bn <= n", "bk <= k",
                     "bg % 8 == 0", "bn % 128 == 0", "bk % 128 == 0"),
        mxu_fraction=1.0,
        param_candidates={
            "bg": (8, 16, 32, 64, 128, 256, 512),
            "bn": (128, 256, 512, 1024),
            "bk": (128, 256, 512, 1024),
        },
        probe_hints={"e": (2, 4)},
    )


def ssd_scan_spec(d_head: int = 64, d_state: int = 128,
                  dtype_bytes: int = 2) -> KernelSpec:
    """Mamba-2 SSD chunked scan (state-space duality, arXiv:2405.21060).

    D: bh (batch*heads), s (sequence).  P: chunk (the SSD chunk length --
    the launch parameter the technique tunes for the attention-free arch).
    Per (bh, s) point: intra-chunk "attention" term ~ 2*chunk*d_head +
    state update terms ~ 4*d_state*d_head / chunk-amortized; we fold the
    chunk-dependence into the grid/tiles and keep flops_per_point for the
    dominant quadratic-in-chunk term.
    """
    return KernelSpec(
        name=f"ssd_scan_h{d_head}_n{d_state}",
        data_params=("bh", "s", "chunkflops"),
        program_params=("chunk",),
        grid=(GridAxis("b", "bh", None), GridAxis("c", "s", "chunk")),
        operands=(
            # Kernel operand order (matches ssd_scan_pallas): x, dt, B, C, A,
            # out, then the inter-chunk state scratch.  dt is broadcast to a
            # lane-aligned (chunk, 128) plane before the pallas_call; the
            # per-head decay rate A is a (1, 128) plane re-fetched per batch
            # row (index map depends on the b axis only).
            Operand("x", ("chunk", d_head), ("b", "c"), dtype_bytes),
            Operand("dt", ("chunk", 128), ("b", "c"), 4),
            Operand("b_proj", ("chunk", d_state), ("b", "c"), dtype_bytes),
            Operand("c_proj", ("chunk", d_state), ("b", "c"), dtype_bytes),
            Operand("decay", (1, 128), ("b",), 4),
            Operand("out", ("chunk", d_head), ("b", "c"), dtype_bytes,
                    is_output=True),
            Operand("state", (d_state, d_head), (), 4, vmem_only=True),
            # f32 (chunk, chunk) gate / score intermediates kept in VMEM.
            Operand("scores", ("chunk", "chunk"), (), 4, vmem_only=True),
        ),
        # dominant intra-chunk matmul term: 2 * chunk * d_head per point is
        # chunk-dependent; expressed by treating "chunkflops" as a data param
        # set to 1 and scaling flops in the driver; simpler: use mean chunk
        # cost at reference chunk 256.
        flops_per_point=2.0 * 256 * 1.0 + 4.0 * d_state,
        constraints=("chunk <= s", "chunk % 128 == 0"),
        mxu_fraction=0.7,
        param_candidates={"chunk": (128, 256, 512, 1024, 2048)},
        fit_vars={"mem_step": ("chunk",), "cmp_step": ("chunk",),
                  "ovh_step": ("chunk",)},
        probe_hints={"bh": (2, 8), "chunkflops": (1,)},
    )


# ---------------------------------------------------------------------------
# Polybench/GPU-analogue suite (the paper's evaluation workloads, Section VI)
# ---------------------------------------------------------------------------
# Each entry mirrors the computational shape of the Polybench kernel on TPU:
# matvec kernels (atax/bicg/mvt/gesummv) tile (rows x cols); matmul-like
# kernels (gemm/mm2/mm3/syrk/syr2k/corr/covar) reuse the matmul template at
# the suite's square sizes; stencils (conv2d/conv3d/fdtd) tile a 2D plane.

def _matvec_spec(name: str, n_mats: int = 1, dtype_bytes: int = 4) -> KernelSpec:
    return KernelSpec(
        name=name,
        data_params=("r", "c"),
        program_params=("br", "bc"),
        grid=(GridAxis("i", "r", "br"), GridAxis("j", "c", "bc")),
        operands=(
            Operand("mat", ("br", "bc"), ("i", "j"), dtype_bytes),
            Operand("vec", (8, "bc"), ("j",), dtype_bytes),
            Operand("out", ("br", 128), ("i",), dtype_bytes, is_output=True),
        ),
        flops_per_point=2.0 * n_mats,
        constraints=("br <= 8 * r", "bc <= 8 * c",
                     "br % 8 == 0", "bc % 128 == 0"),
        mxu_fraction=0.6,
        param_candidates={"br": (8, 16, 32, 64, 128, 256, 512, 1024),
                          "bc": (128, 256, 512, 1024, 2048, 4096)},
        fit_vars={"mem_step": ("br", "bc"), "cmp_step": ("br", "bc"),
                  "ovh_step": ("br", "bc")},
    )


def _stencil_spec(name: str, halo: int, flops: float,
                  dtype_bytes: int = 4) -> KernelSpec:
    return KernelSpec(
        name=name,
        data_params=("r", "c"),
        program_params=("br", "bc"),
        grid=(GridAxis("i", "r", "br"), GridAxis("j", "c", "bc")),
        operands=(
            Operand("inp", ("br", "bc"), ("i", "j"), dtype_bytes),
            Operand("halo_r", (2 * halo, "bc"), ("i", "j"), dtype_bytes),
            Operand("halo_c", ("br", 2 * 128), ("i", "j"), dtype_bytes),
            Operand("out", ("br", "bc"), ("i", "j"), dtype_bytes,
                    is_output=True),
        ),
        flops_per_point=flops,
        constraints=("br <= 8 * r", "bc <= 8 * c",
                     "br % 8 == 0", "bc % 128 == 0"),
        mxu_fraction=0.0,   # stencils are VPU work
        param_candidates={"br": (8, 16, 32, 64, 128, 256, 512),
                          "bc": (128, 256, 512, 1024, 2048)},
        fit_vars={"mem_step": ("br", "bc"), "cmp_step": ("br", "bc"),
                  "ovh_step": ("br", "bc")},
    )


def _reduction_spec(name: str, flops: float = 1.0,
                    dtype_bytes: int = 4) -> KernelSpec:
    return KernelSpec(
        name=name,
        data_params=("r", "c"),
        program_params=("br",),
        grid=(GridAxis("i", "r", "br"), GridAxis("j", "c", None)),
        operands=(
            Operand("inp", ("br", "c"), ("i", "j"), dtype_bytes),
            Operand("out", (8, 128), (), dtype_bytes, is_output=True),
        ),
        flops_per_point=flops,
        constraints=("br <= 8 * r", "br % 8 == 0"),
        mxu_fraction=0.0,
        param_candidates={"br": (8, 16, 32, 64, 128, 256, 512, 1024)},
        fit_vars={"mem_step": ("br",), "cmp_step": ("br",),
                  "ovh_step": ("br",)},
    )


def polybench_suite() -> dict[str, KernelSpec]:
    """The Polybench/GPU-analogue benchmark suite (paper Table I rows)."""
    suite: dict[str, KernelSpec] = {}
    suite["gemm"] = matmul_spec(dtype_bytes=4)
    suite["gemm"].name = "gemm"
    for nm in ("mm2_k1", "mm2_k2", "mm3_k1", "mm3_k2", "mm3_k3",
               "syrk", "syr2k", "corr", "covar"):
        s = matmul_spec(dtype_bytes=4)
        s.name = nm
        if nm in ("syr2k",):
            s.flops_per_point = 4.0
        if nm in ("corr", "covar"):
            s.mxu_fraction = 0.8
        suite[nm] = s
    for nm, k in (("atax_k1", 1), ("atax_k2", 1), ("bicg_k1", 1),
                  ("bicg_k2", 1), ("mvt_k1", 1), ("mvt_k2", 1),
                  ("gesummv", 2)):
        suite[nm] = _matvec_spec(nm, n_mats=k)
    suite["conv2d"] = _stencil_spec("conv2d", halo=8, flops=17.0)
    suite["conv3d"] = _stencil_spec("conv3d", halo=8, flops=53.0)
    for nm in ("fdtd_step1", "fdtd_step2", "fdtd_step3"):
        suite[nm] = _stencil_spec(nm, halo=8, flops=5.0)
    for nm, fl in (("reduce", 1.0), ("mean", 2.0), ("std", 4.0)):
        suite[nm] = _reduction_spec(nm, flops=fl)
    for nm in ("gramschmidt_k1", "gramschmidt_k2", "gramschmidt_k3"):
        suite[nm] = _matvec_spec(nm)
    return suite


POLYBENCH_SUITE = tuple(polybench_suite().keys())
