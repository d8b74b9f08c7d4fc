"""Per-layer readers for a cell whose model family counts its own work.

``bench/work.py`` dispatches its counts on the configuration's family and
knows two; ``bench/readers.py`` reads through it.  A family module under
``bench/models`` may instead state ``kernel_calls(c, batch, seq)`` and
``forward_flops(c, batch, seq, logit_positions)``, with ``work``'s
signatures, and its cell's metrics read them here by the same arithmetic.
A reader that finds nothing to read returns ``None``.
"""

from __future__ import annotations

from bench import readers
from bench import trace_reduce as tr

# Device op names of the kernels these readers time: the tuned kernels',
# and the held experts' ragged matmul (``kernels/moe_experts.py``).
KERNEL_OPS = {**readers.KERNEL_OPS,
              "moe_experts": r"^moe_experts_pallas(\.\d+)?$"}


def kernel_roofline(t, kernel: str, launches: int = 1) -> float | None:
    """The least time the chip could take for the kernel's work in the
    window, over the summed device time of its calls, in %.  The family's
    count is of one unit of work that ``launches`` calls share."""
    calls = tr.op_events(t.trace, KERNEL_OPS[kernel], t.t0, t.t1)
    if not calls:
        return None
    mix = t.cell.traffic
    need = t.cell.family.kernel_calls(t.cell.config, mix["batch"],
                                      mix["seq_len"])[kernel]
    least, bound = need.min_seconds(t.peaks)
    spent = sum(e - s for _, s, e in calls) * 1e-9
    units = len(calls) / launches
    print(f"[roofline] {kernel}: {len(calls)} calls, {spent:.6f} s on the "
          f"device, {bound}-bound least time {least:.6f} s for each "
          f"{launches} of them", flush=True)
    return 100.0 * units * least / spent


def forward_mfu(t) -> float | None:
    """Needed FLOPs of the forward steps the window ran, over the window
    times the chip's bf16 peak, in %."""
    steps = readers.program_count(t, readers.FORWARD_PROGRAM)
    if not steps:
        return None
    mix = t.cell.traffic
    flops = t.cell.family.forward_flops(
        t.cell.config, mix["batch"], mix["seq_len"], mix["logit_positions"])
    return 100.0 * steps * flops / (t.window_ns * 1e-9
                                    * t.peaks["bf16_flops_per_s"])
