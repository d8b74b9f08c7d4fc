"""Arithmetic the per-layer metrics share.  Each metric is a file of its own
in ``bench/metrics``; those that compute the same quantity for different
cells call one function here.  A reader that finds nothing to read
returns ``None`` and the metric is left out of the result."""

from __future__ import annotations

from bench import trace_reduce as tr
from bench import work

# How each tuned kernel is named in the device trace: its custom call
# takes the name of the jitted function that wraps the ``pallas_call``.
KERNEL_OPS = {"flash_attention": r"^flash_attention_pallas(\.\d+)?$",
              "ssd_scan": r"^ssd_scan_pallas(\.\d+)?$"}
# Programs, by the jitted function that made them.
FORWARD_PROGRAM = "jit_bench_forward"
DECODE_PROGRAM = "jit_step"
PREFILL_PROGRAM = "jit_prefill_chunk_step"


def idle_share(t) -> float:
    return 100.0 * (1.0 - t.busy_ns / t.window_ns)


def program_share(t, program: str) -> float | None:
    """Share of the device's busy time spent in ``program``, in %."""
    ns = t.programs.get(program)
    if not ns or not t.busy_ns:
        return None
    return 100.0 * ns / t.busy_ns


def program_runs(t, program: str, chip: int = 0) -> list[float]:
    """Device seconds of each execution of ``program`` in the window."""
    return [(e - s) * 1e-9 for name, s, e in t.trace.programs[chip]
            if name == program and s >= t.t0 and e <= t.t1]


def program_count(t, program: str, chip: int = 0) -> float:
    """Executions of ``program`` in the window, each counted by the share
    of its device time inside it: the host's and the device's clocks in a
    trace agree only to some microseconds."""
    return sum((min(e, t.t1) - max(s, t.t0)) / (e - s)
               for name, s, e in t.trace.programs[chip]
               if name == program and e > t.t0 and s < t.t1 and e > s)


def kernel_roofline(t, kernel: str) -> float | None:
    """The least time the chip could take for the kernel's calls in the
    window, over their summed device time, in %."""
    calls = tr.op_events(t.trace, KERNEL_OPS[kernel], t.t0, t.t1)
    if not calls:
        return None
    mix = t.cell.traffic
    need = work.kernel_calls(t.cell.config, mix["batch"],
                             mix["seq_len"])[kernel]
    least, bound = need.min_seconds(t.peaks)
    spent = sum(e - s for _, s, e in calls) * 1e-9
    print(f"[roofline] {kernel}: {len(calls)} calls, {spent:.6f} s on the "
          f"device, {bound}-bound least time {least:.6f} s a call",
          flush=True)
    return 100.0 * len(calls) * least / spent


def forward_mfu(t) -> float | None:
    """Needed FLOPs of the forward steps the window ran, over the window
    times the chip's bf16 peak, in %."""
    steps = program_count(t, FORWARD_PROGRAM)
    if not steps:
        return None
    mix = t.cell.traffic
    flops = work.forward_step(t.cell.config, mix["batch"], mix["seq_len"],
                              mix["logit_positions"])
    return 100.0 * steps * flops / (t.window_ns * 1e-9
                                    * t.peaks["bf16_flops_per_s"])


def decode_step_ms(t) -> float | None:
    """Median device time of one decode-step execution, in ms."""
    runs = sorted(program_runs(t, DECODE_PROGRAM))
    if not runs:
        return None
    return 1e3 * runs[len(runs) // 2]
