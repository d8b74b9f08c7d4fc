"""Hardware parameters H and the ground-truth device backends.

The paper fixes a target device (GTX 1080Ti), collects hardware parameters by
microbenchmarking + vendor tables, and treats the device as an opaque oracle
that its CUPTI-based profiler probes.  This build targets TPU v5e; since the
container is CPU-only, the opaque oracle role is played by ``V5eSimulator`` --
a timing model of one v5e TensorCore that is deliberately *richer* (extra
nonlinearities: DMA-size-dependent bandwidth, lane/sublane padding waste,
MXU-utilization curves, grid dispatch overhead, imperfect pipeline overlap)
and *noisier* (lognormal profiling noise) than anything the KLARAPTOR fitter
assumes.  The fitter may only call ``probe``; nothing in core/fitting.py or
core/perf_model.py reads the simulator internals.

``InterpretTimer`` wall-clocks real Pallas interpret-mode kernels on CPU and
exposes the same probe interface, proving the pipeline is backend-agnostic.
"""

from __future__ import annotations

import dataclasses
import math
import time
from dataclasses import dataclass
from typing import Callable, Mapping, Sequence

import numpy as np

__all__ = [
    "HardwareParams", "V5E", "V5P", "DEVICE_KINDS", "hardware_for",
    "target_hardware", "ProbeRecord", "ProbeBatch", "RowProbe",
    "DeviceModel", "KernelTraffic", "TrafficTable", "TrafficOperand",
    "V5eSimulator", "InterpretTimer", "DTYPE_BYTES", "dtype_bytes",
]

# Canonical dtype-width table, keyed by HLO short names.  This is the single
# source of truth for "how many bytes does one element move": the HLO
# collective parser (analysis/hlo.py) and the introspection cost walk
# (repro/introspect) both consume it, so a new dtype is added exactly once.
DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# numpy/jax dtype names -> HLO short names (for dtype_bytes lookups on
# dtype objects rather than HLO text).
_NP_TO_HLO = {
    "bool": "pred", "int8": "s8", "uint8": "u8", "int16": "s16",
    "uint16": "u16", "bfloat16": "bf16", "float16": "f16", "int32": "s32",
    "uint32": "u32", "float32": "f32", "int64": "s64", "uint64": "u64",
    "float64": "f64", "complex64": "c64", "complex128": "c128",
    "float8_e4m3fn": "f8e4m3fn", "float8_e5m2": "f8e5m2",
}


def dtype_bytes(dt) -> int:
    """Bytes per element for an HLO short name or a numpy/jax dtype.

    Accepts "bf16"-style HLO names, dtype objects, and dtype names
    ("bfloat16"); unknown dtype objects fall back to their itemsize.
    """
    if isinstance(dt, str) and dt in DTYPE_BYTES:
        return DTYPE_BYTES[dt]
    name = getattr(dt, "name", None) or str(dt)
    hlo = _NP_TO_HLO.get(name)
    if hlo is not None:
        return DTYPE_BYTES[hlo]
    try:
        return int(np.dtype(dt).itemsize)
    except TypeError:
        raise KeyError(f"unknown dtype {dt!r}")


@dataclass(frozen=True)
class HardwareParams:
    """Hardware parameters H (paper Section II): fixed per target device.

    ``vmem_bytes`` is the physical VMEM of one TensorCore (the pipeline
    occupancy model's capacity).  ``vmem_limit_bytes`` is the scoped VMEM
    budget one kernel may use: the tuner admits only configs whose modelled
    footprint fits it, and every kernel passes the same number to Mosaic as
    ``vmem_limit_bytes``, so a config the tuner admits is one the compiler
    accepts.
    """

    name: str
    peak_flops_bf16: float          # MXU peak, FLOP/s
    peak_flops_f32: float
    hbm_bw: float                   # bytes/s
    vmem_bytes: int
    vmem_limit_bytes: int
    ici_bw_per_link: float          # bytes/s per ICI link
    ici_links: int                  # links per chip (2D torus: 4)
    mxu_dim: int = 128
    lanes: int = 128
    sublanes_f32: int = 8
    hbm_bytes: int = 16 * 2**30
    dcn_bw: float = 25e9            # bytes/s per host, cross-pod

    def sublanes(self, dtype_bytes: int) -> int:
        # Packed types double the sublane granularity: bf16 -> 16, int8 -> 32.
        return self.sublanes_f32 * max(1, 4 // dtype_bytes)


# TPU v5e.  Peaks: Google Cloud TPU documentation, "TPU v5e" (197 TFLOP/s
# bf16, 16 GiB HBM at 819 GB/s, 1,600 Gbit/s ICI = 4 links x 50 GB/s).  No
# f32 peak is published; a quarter of bf16 is assumed.  VMEM: 128 MiB per
# TensorCore, of which Mosaic grants a kernel 16 MiB unless the kernel asks
# for more.  The budget asks for half of it: enough for the largest tiles
# the candidate grids hold (a 2048x2048 flash tile counts 26.5 MiB in the
# compiler), with the other half left for the compiler's own scratch that
# the footprint model does not see.
V5E = HardwareParams(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    peak_flops_f32=49.25e12,
    hbm_bw=819e9,
    vmem_bytes=128 * 2**20,
    vmem_limit_bytes=64 * 2**20,
    ici_bw_per_link=50e9,
    ici_links=4,
    hbm_bytes=16 * 2**30,
)

# TPU v5p: a second device profile, so performance-portability experiments
# (the paper's point that optimal configs differ across devices) re-tune
# against it.  Peaks: Google Cloud TPU documentation, "TPU v5p" (459 TFLOP/s
# bf16, 95 GiB HBM at 2,765 GB/s, 4,800 Gbit/s ICI = 6 links x 100 GB/s).
# f32 peak and VMEM are assumed as for v5e; no v5p has run this code.
V5P = HardwareParams(
    name="tpu_v5p",
    peak_flops_bf16=459e12,
    peak_flops_f32=114.75e12,
    hbm_bw=2765e9,
    vmem_bytes=128 * 2**20,
    vmem_limit_bytes=64 * 2**20,
    ici_bw_per_link=100e9,
    ici_links=6,
    hbm_bytes=95 * 2**30,
)

# ``jax.Device.device_kind`` -> HardwareParams.  A chip that is not here is
# an error (``hardware_for``), never a silent v5e default.
DEVICE_KINDS = {"TPU v5 lite": V5E, "TPU v5": V5P}


def hardware_for(device_kind: str) -> HardwareParams:
    """HardwareParams of a chip, by its ``device_kind``."""
    try:
        return DEVICE_KINDS[device_kind]
    except KeyError:
        raise KeyError(
            f"no hardware parameters for device kind {device_kind!r}; "
            f"known kinds: {sorted(DEVICE_KINDS)}") from None


def target_hardware() -> HardwareParams:
    """HardwareParams of the chip this process compiles kernels for.

    On a TPU backend: the table entry of ``jax.devices()[0]`` (an unknown
    chip raises).  On any other backend -- the CPU test path, where kernels
    are interpreted or compiled for a described v5e -- the build's target,
    ``V5E``.
    """
    import jax

    if jax.default_backend() == "tpu":
        return hardware_for(jax.devices()[0].device_kind)
    return V5E


@dataclass
class ProbeRecord:
    """What one profiled execution returns (the CUPTI-event analogue).

    The customized profiler of Section V-D collects "exactly the information
    required for the model and nothing else": total time plus the per-kernel
    low-level counters the performance model consumes.
    """

    total_time_s: float
    mem_time_s: float              # aggregate DMA busy time
    compute_time_s: float          # aggregate MXU/VPU busy time
    grid_steps: int
    vmem_stage_bytes: int
    counters: dict[str, float] = dataclasses.field(default_factory=dict)


@dataclass
class ProbeBatch:
    """Struct-of-arrays probe results for a whole candidate table.

    Each timing field has shape ``(repeats, n_configs)``; the per-config
    workload descriptors (``grid_steps``, ``vmem_stage_bytes``) have shape
    ``(n_configs,)``.  This is what ``collect`` consumes to derive per-step
    metric targets in one ndarray pass.
    """

    total_time_s: np.ndarray
    mem_time_s: np.ndarray
    compute_time_s: np.ndarray
    grid_steps: np.ndarray
    vmem_stage_bytes: np.ndarray

    @property
    def n_executions(self) -> int:
        return int(self.total_time_s.size)


@dataclass
class RowProbe:
    """Per-row probe summary when every row may use a different repeat count.

    Search strategies (repro/search) refine promising configurations with more
    repeats than the rest of the table -- successive halving probes everything
    once, then re-probes survivors.  All timing fields are (n,) medians over
    each row's own repeats; ``device_seconds`` is the simulated device time
    actually spent on each row (sum over its executions), which is what a
    ``SearchBudget`` charges.
    """

    total_time_s: np.ndarray       # (n,) median over the row's repeats
    mem_time_s: np.ndarray
    compute_time_s: np.ndarray
    grid_steps: np.ndarray
    vmem_stage_bytes: np.ndarray
    device_seconds: np.ndarray     # (n,) total probe time spent per row
    repeats: np.ndarray            # (n,) int64 executions per row

    @property
    def n_executions(self) -> int:
        return int(np.sum(self.repeats))


class DeviceModel:
    """Opaque device oracle interface (what CUPTI+GPU is in the paper)."""

    hw: HardwareParams

    def fingerprint(self) -> dict:
        """JSON-able identity of this oracle, folded into driver-cache keys:
        probing a different oracle must not hit another oracle's artifacts."""
        return {"class": type(self).__name__}

    def probe(self, workload: "KernelTraffic", rng: np.random.RandomState
              ) -> ProbeRecord:
        raise NotImplementedError

    def probe_batch(self, table: "TrafficTable",
                    rng: np.random.RandomState,
                    repeats: int = 1) -> ProbeBatch:
        """Probe every launch in ``table`` ``repeats`` times.

        Generic fallback: one ``probe`` call per (repeat, config).  Backends
        with vectorized physics (``V5eSimulator``) override this with a
        single ndarray pass over the whole table.
        """
        n = len(table)
        tot = np.empty((repeats, n))
        mem = np.empty((repeats, n))
        cmp_ = np.empty((repeats, n))
        for i in range(n):
            w = table.row(i)
            for r in range(repeats):
                rec = self.probe(w, rng)
                tot[r, i] = rec.total_time_s
                mem[r, i] = rec.mem_time_s
                cmp_[r, i] = rec.compute_time_s
        return ProbeBatch(tot, mem, cmp_, np.asarray(table.grid_steps),
                          np.asarray(table.vmem_stage_bytes))

    def probe_rows(self, table: "TrafficTable",
                   rng: np.random.RandomState,
                   repeats: np.ndarray | int = 1) -> RowProbe:
        """Probe row ``i`` of ``table`` ``repeats[i]`` times (medians per row).

        Per-row repeat counts are what budgeted search strategies need:
        successive halving probes the whole table once and spends further
        repeats only on survivors.  Rows are grouped by repeat count and each
        group goes through ``probe_batch``, so backends with vectorized
        physics stay vectorized (one pass per distinct repeat value, of which
        a halving schedule has only a handful).
        """
        n = len(table)
        reps = np.maximum(
            np.broadcast_to(np.asarray(repeats, dtype=np.int64), (n,)), 1)
        tot = np.empty(n)
        mem = np.empty(n)
        cmp_ = np.empty(n)
        spent = np.empty(n)
        for r in np.unique(reps):
            idx = np.flatnonzero(reps == r)
            batch = self.probe_batch(table.select(idx), rng, repeats=int(r))
            tot[idx] = np.median(batch.total_time_s, axis=0)
            mem[idx] = np.median(batch.mem_time_s, axis=0)
            cmp_[idx] = np.median(batch.compute_time_s, axis=0)
            spent[idx] = np.sum(batch.total_time_s, axis=0)
        return RowProbe(
            total_time_s=tot,
            mem_time_s=mem,
            compute_time_s=cmp_,
            grid_steps=np.asarray(table.grid_steps),
            vmem_stage_bytes=np.asarray(table.vmem_stage_bytes),
            device_seconds=spent,
            repeats=np.array(reps),
        )

    def true_time_batch(self, table: "TrafficTable") -> np.ndarray:
        raise NotImplementedError(
            f"{type(self).__name__} has no noise-free batched oracle")


@dataclass
class KernelTraffic:
    """Analytic workload description of one kernel launch at concrete (D, P).

    Produced by a KernelSpec (core/kernel_spec.py).  ``tiles_in``/``tiles_out``
    list (tile_shape, fetch_count) per operand -- fetch_count already accounts
    for block residency/reuse across grid steps.  dtype_bytes is per-operand.
    """

    grid_steps: int
    flops_total: float
    tiles_in: Sequence[tuple[tuple[int, ...], int, int]]   # (shape, fetches, dtype_bytes)
    tiles_out: Sequence[tuple[tuple[int, ...], int, int]]
    vmem_stage_bytes: int
    # Fraction of FLOPs that go to the MXU (matmul) vs the VPU (elementwise).
    mxu_fraction: float = 1.0


@dataclass
class TrafficOperand:
    """Columnar per-operand traffic for a whole candidate table.

    ``shapes`` is (n_configs, ndim): one tile shape per config.  ``fetches``
    is (n_configs,): HBM fetch counts already accounting for block residency.
    """

    name: str
    shapes: np.ndarray
    fetches: np.ndarray
    dtype_bytes: int
    is_output: bool


@dataclass
class TrafficTable:
    """Struct-of-arrays analogue of ``KernelTraffic`` over many configs.

    One data size D, ``n`` candidate configurations: every field is an
    ndarray over the config axis so device oracles can evaluate the whole
    table without a Python loop (the batched face of Section IV step 1).
    """

    grid_steps: np.ndarray          # (n,) int64
    flops_total: np.ndarray         # (n,) float64
    operands: list[TrafficOperand]
    vmem_stage_bytes: np.ndarray    # (n,) int64
    mxu_fraction: float = 1.0

    def __len__(self) -> int:
        return int(self.grid_steps.shape[0])

    def select(self, index) -> "TrafficTable":
        """New table keeping rows selected by a boolean mask or index array.

        Mirrors ``CandidateTable.select`` so search strategies can probe a
        subset of the candidate table through the same batched oracle path.
        """
        return TrafficTable(
            grid_steps=self.grid_steps[index],
            flops_total=self.flops_total[index],
            operands=[TrafficOperand(
                name=op.name,
                shapes=op.shapes[index],
                fetches=op.fetches[index],
                dtype_bytes=op.dtype_bytes,
                is_output=op.is_output,
            ) for op in self.operands],
            vmem_stage_bytes=self.vmem_stage_bytes[index],
            mxu_fraction=self.mxu_fraction,
        )

    def row(self, i: int) -> KernelTraffic:
        """Scalar KernelTraffic view of config ``i`` (generic-probe fallback)."""
        tiles_in, tiles_out = [], []
        for op in self.operands:
            rec = (tuple(int(d) for d in op.shapes[i]),
                   int(op.fetches[i]), op.dtype_bytes)
            (tiles_out if op.is_output else tiles_in).append(rec)
        return KernelTraffic(
            grid_steps=int(self.grid_steps[i]),
            flops_total=float(self.flops_total[i]),
            tiles_in=tiles_in,
            tiles_out=tiles_out,
            vmem_stage_bytes=int(self.vmem_stage_bytes[i]),
            mxu_fraction=self.mxu_fraction,
        )


def _padded_tile_bytes(shape: tuple[int, ...], dtype_bytes: int,
                       hw: HardwareParams) -> int:
    """VMEM tile footprint after (sublane, lane) padding of the last 2 dims."""
    if not shape:
        return dtype_bytes
    dims = list(shape)
    dims[-1] = math.ceil(dims[-1] / hw.lanes) * hw.lanes
    if len(dims) >= 2:
        sl = hw.sublanes(dtype_bytes)
        dims[-2] = math.ceil(dims[-2] / sl) * sl
    n = 1
    for d in dims:
        n *= d
    return n * dtype_bytes


class V5eSimulator(DeviceModel):
    """Ground-truth stand-in for a v5e TensorCore.

    Hidden physics (all invisible to the fitter):
      * DMA efficiency ramps with transfer size:  eff = max_eff * s/(s + s_half)
        (classic latency/bandwidth curve; s_half ~ 96 KiB).
      * Tile padding to (sublane, lane) granularity wastes bandwidth.
      * MXU utilization degrades for matmul dims below mxu_dim and for
        non-multiples (systolic fill + padding).
      * Fixed per-grid-step dispatch overhead (scalar core + DMA issue).
      * Software pipelining overlaps DMA and compute only when >= 2 stage
        buffers fit VMEM; overlap is imperfect (leak factor) and has a
        pipeline fill cost of one stage.
      * Multiplicative lognormal measurement noise per probe.
    """

    def __init__(self, hw: HardwareParams = V5E, noise: float = 0.04,
                 seed: int = 0):
        self.hw = hw
        self.noise = noise
        self._seed = seed

    def fingerprint(self) -> dict:
        return {"class": type(self).__name__, "hw": self.hw.name,
                "noise": self.noise, "seed": self._seed}

    # -- hidden physics ------------------------------------------------------
    def _dma_eff(self, transfer_bytes: float) -> float:
        s_half = 96 * 1024.0
        return 0.98 * transfer_bytes / (transfer_bytes + s_half)

    def _mxu_eff(self, workload: KernelTraffic) -> float:
        # Utilization estimated from stage shape of the *first* input tile
        # (for matmul-like kernels this is the (bm, bk) tile).
        if not workload.tiles_in:
            return 0.6
        shape = workload.tiles_in[0][0]
        eff = 1.0
        d = self.hw.mxu_dim
        for dim in shape[-2:]:
            frac_fill = min(dim, d) / d           # small dims underfill
            pad = dim / (math.ceil(dim / d) * d)  # non-multiples pad
            eff *= (0.25 + 0.75 * frac_fill) * pad
        return max(eff, 0.05)

    def _times(self, w: KernelTraffic) -> tuple[float, float, float]:
        hw = self.hw
        mem_bytes = 0.0
        weighted_eff = 0.0
        for shape, fetches, db in list(w.tiles_in) + list(w.tiles_out):
            tb = _padded_tile_bytes(shape, db, hw)
            b = tb * fetches
            mem_bytes += b
            weighted_eff += b * self._dma_eff(tb)
        dma_eff = (weighted_eff / mem_bytes) if mem_bytes else 1.0
        t_mem = mem_bytes / (hw.hbm_bw * dma_eff)
        peak = hw.peak_flops_bf16 * w.mxu_fraction + \
            (hw.peak_flops_bf16 / 8.0) * (1.0 - w.mxu_fraction)
        t_cmp = w.flops_total / (peak * self._mxu_eff(w))
        t_ovh = w.grid_steps * 1.1e-6  # dispatch + DMA issue per step
        return t_mem, t_cmp, t_ovh

    def _total(self, w: KernelTraffic) -> tuple[float, float, float]:
        t_mem, t_cmp, t_ovh = self._times(w)
        buffers = self.hw.vmem_bytes // max(w.vmem_stage_bytes, 1)
        if buffers >= 2:
            fill = (t_mem / max(w.grid_steps, 1))  # pipeline fill: one stage
            total = max(t_mem, t_cmp) + 0.08 * min(t_mem, t_cmp) + fill + t_ovh
        else:
            total = t_mem + t_cmp + t_ovh  # no double buffering: serialized
        return total, t_mem, t_cmp

    # -- vectorized hidden physics (same formulas, whole table at once) ------
    def _padded_tile_bytes_batch(self, shapes: np.ndarray,
                                 dtype_bytes: int) -> np.ndarray:
        """(n, ndim) tile shapes -> (n,) padded VMEM footprints in bytes."""
        dims = np.asarray(shapes, dtype=np.float64).copy()
        hw = self.hw
        dims[:, -1] = np.ceil(dims[:, -1] / hw.lanes) * hw.lanes
        if dims.shape[1] >= 2:
            sl = hw.sublanes(dtype_bytes)
            dims[:, -2] = np.ceil(dims[:, -2] / sl) * sl
        return np.prod(dims, axis=1) * dtype_bytes

    def _mxu_eff_batch(self, t: TrafficTable) -> np.ndarray:
        inputs = [op for op in t.operands if not op.is_output]
        if not inputs:
            return np.full(len(t), 0.6)
        shape = np.asarray(inputs[0].shapes, dtype=np.float64)[:, -2:]
        d = float(self.hw.mxu_dim)
        eff = np.ones(shape.shape[0])
        for j in range(shape.shape[1]):
            dim = shape[:, j]
            frac_fill = np.minimum(dim, d) / d
            pad = dim / (np.ceil(dim / d) * d)
            eff = eff * (0.25 + 0.75 * frac_fill) * pad
        return np.maximum(eff, 0.05)

    def _times_batch(self, t: TrafficTable
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        hw = self.hw
        n = len(t)
        mem_bytes = np.zeros(n)
        weighted_eff = np.zeros(n)
        for op in t.operands:
            tb = self._padded_tile_bytes_batch(op.shapes, op.dtype_bytes)
            b = tb * np.asarray(op.fetches, dtype=np.float64)
            mem_bytes += b
            weighted_eff += b * self._dma_eff(tb)
        dma_eff = np.where(mem_bytes > 0, weighted_eff / np.maximum(mem_bytes, 1.0),
                           1.0)
        t_mem = mem_bytes / (hw.hbm_bw * dma_eff)
        peak = hw.peak_flops_bf16 * t.mxu_fraction + \
            (hw.peak_flops_bf16 / 8.0) * (1.0 - t.mxu_fraction)
        t_cmp = np.asarray(t.flops_total, dtype=np.float64) / \
            (peak * self._mxu_eff_batch(t))
        t_ovh = np.asarray(t.grid_steps, dtype=np.float64) * 1.1e-6
        return t_mem, t_cmp, t_ovh

    def _total_batch(self, t: TrafficTable
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        t_mem, t_cmp, t_ovh = self._times_batch(t)
        stage = np.maximum(np.asarray(t.vmem_stage_bytes, dtype=np.float64), 1.0)
        buffers = np.floor(self.hw.vmem_bytes / stage)
        steps = np.maximum(np.asarray(t.grid_steps, dtype=np.float64), 1.0)
        fill = t_mem / steps
        overlapped = (np.maximum(t_mem, t_cmp) + 0.08 * np.minimum(t_mem, t_cmp)
                      + fill + t_ovh)
        serialized = t_mem + t_cmp + t_ovh
        total = np.where(buffers >= 2, overlapped, serialized)
        return total, t_mem, t_cmp

    # -- oracle interface ------------------------------------------------------
    def probe(self, workload: KernelTraffic,
              rng: np.random.RandomState | None = None) -> ProbeRecord:
        rng = rng or np.random.RandomState(self._seed)
        total, t_mem, t_cmp = self._total(workload)
        n = lambda: float(np.exp(rng.normal(0.0, self.noise)))
        return ProbeRecord(
            total_time_s=total * n(),
            mem_time_s=t_mem * n(),
            compute_time_s=t_cmp * n(),
            grid_steps=workload.grid_steps,
            vmem_stage_bytes=workload.vmem_stage_bytes,
        )

    def probe_batch(self, table: TrafficTable,
                    rng: np.random.RandomState | None = None,
                    repeats: int = 1) -> ProbeBatch:
        """One ndarray pass over the whole candidate table, then noise.

        Replaces ``repeats * n_configs`` scalar probe calls with a single
        evaluation of the hidden physics plus one lognormal draw per
        (field, repeat, config).
        """
        rng = rng or np.random.RandomState(self._seed)
        total, t_mem, t_cmp = self._total_batch(table)
        n = len(table)
        noise = np.exp(rng.normal(0.0, self.noise, size=(3, repeats, n)))
        return ProbeBatch(
            total_time_s=total[None, :] * noise[0],
            mem_time_s=t_mem[None, :] * noise[1],
            compute_time_s=t_cmp[None, :] * noise[2],
            grid_steps=np.asarray(table.grid_steps),
            vmem_stage_bytes=np.asarray(table.vmem_stage_bytes),
        )

    def true_time(self, workload: KernelTraffic) -> float:
        """Noise-free time -- used ONLY by evaluation harnesses (the
        'exhaustive search ground truth' column of Table I), never by the
        fitter."""
        return self._total(workload)[0]

    def true_time_batch(self, table: TrafficTable) -> np.ndarray:
        """Noise-free times for every config in the table (evaluation only)."""
        return self._total_batch(table)[0]


class InterpretTimer(DeviceModel):
    """Wall-clock probe of a real callable (Pallas interpret-mode kernel).

    ``runner(D, P) -> callable`` must return a zero-arg function executing the
    kernel once on real buffers.  Used by tests to drive the full KLARAPTOR
    pipeline against genuine executions instead of the simulator.
    """

    def __init__(self, runner: Callable[..., Callable[[], None]],
                 hw: HardwareParams = V5E, repeats: int = 3):
        self.hw = hw
        self._runner = runner
        self._repeats = repeats

    def probe_call(self, fn: Callable[[], None], grid_steps: int,
                   vmem_stage_bytes: int) -> ProbeRecord:
        fn()  # warmup (trace/compile)
        best = math.inf
        for _ in range(self._repeats):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return ProbeRecord(
            total_time_s=best,
            mem_time_s=best * 0.5,
            compute_time_s=best * 0.5,
            grid_steps=grid_steps,
            vmem_stage_bytes=vmem_stage_bytes,
        )
