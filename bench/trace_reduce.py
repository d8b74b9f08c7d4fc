"""From a profiler trace (``.xplane.pb``) to device intervals and their sums.

Two stages.  ``load`` reads the file with JAX's own reader into a
``Trace``: per chip, the device operations (the ``XLA Ops`` line) and the
compiled programs they belong to (the ``XLA Modules`` line), and the
host's spans, all on the trace's one clock in nanoseconds.  The other
functions reduce a ``Trace`` inside a window: the union of the intervals
in which an operation ran (busy time), the gaps between them and what
the host was doing in each, and summed device time per program or per
operation.  A ``Trace`` round-trips through JSON, so the reduction is
tested on a recorded trace without the chip.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

__all__ = ["Trace", "load", "find_xplane", "window_of", "busy_ns",
           "idle_gaps", "label_gaps", "program_ns", "op_ns",
           "union_intervals"]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
_PROGRAM_SUFFIX = re.compile(r"\(\d+\)$")


@dataclass
class Trace:
    # per chip: [name, start_ns, end_ns]
    ops: list = field(default_factory=list)
    programs: list = field(default_factory=list)
    # [thread, name, start_ns, end_ns]
    host: list = field(default_factory=list)

    def to_json(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"ops": self.ops, "programs": self.programs,
                       "host": self.host}, f)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            return cls(**json.load(f))


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: an
    operation's event carries its whole HLO instruction."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def program_name(module: str) -> str:
    """``jit_step(12)`` -> ``jit_step``: the name a jitted function gives
    its program, without the trace's run number."""
    return _PROGRAM_SUFFIX.sub("", module)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips = {}
    host = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            missing = {OPS_LINE, PROGRAMS_LINE} - set(lines)
            if missing:
                raise ValueError(
                    f"{plane.name} has no line {sorted(missing)}; lines: "
                    f"{sorted(lines)}")
            chips[int(m.group(1))] = (
                [[op_name(e.name), e.start_ns, e.end_ns]
                 for e in lines[OPS_LINE].events],
                [[program_name(e.name), e.start_ns, e.end_ns]
                 for e in lines[PROGRAMS_LINE].events])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend([line.name, e.name, e.start_ns, e.end_ns]
                            for e in line.events if e.duration_ns > 0)
    if not chips:
        raise ValueError(f"{path} holds no TPU device plane")
    order = sorted(chips)
    return Trace(ops=[chips[i][0] for i in order],
                 programs=[chips[i][1] for i in order], host=host)


def window_of(trace: Trace, span: str = WINDOW_SPAN) -> tuple[float, float]:
    """The start and end of the host span that marks the traced window."""
    hits = [(s, e) for _, name, s, e in trace.host if name == span]
    if len(hits) != 1:
        raise ValueError(f"expected one {span!r} host span, found "
                         f"{len(hits)}")
    return hits[0]


def _clipped(events, t0: float, t1: float):
    for ev in events:
        s, e = max(ev[1], t0), min(ev[2], t1)
        if e > s:
            yield ev[0], s, e


def union_intervals(intervals) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) pairs."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace, t0: float, t1: float) -> float:
    """Nanoseconds in which any operation ran, averaged over the chips."""
    per_chip = [sum(e - s for s, e in union_intervals(
        (s, e) for _, s, e in _clipped(ops, t0, t1))) for ops in trace.ops]
    return sum(per_chip) / len(per_chip)


def idle_gaps(trace: Trace, t0: float, t1: float, chip: int = 0
              ) -> list[tuple[float, float]]:
    """The intervals of the window in which no operation ran on ``chip``."""
    gaps, cursor = [], t0
    for s, e in union_intervals(
            (s, e) for _, s, e in _clipped(trace.ops[chip], t0, t1)):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if t1 > cursor:
        gaps.append((cursor, t1))
    return gaps


def label_gaps(trace: Trace, gaps, skip=(WINDOW_SPAN,)) -> dict[str, float]:
    """Idle nanoseconds by what the host was doing: each gap goes to the
    host span that covers most of it (the innermost, shortest, on a tie),
    or to ``(no host span)``.  ``gaps`` are sorted and disjoint."""
    spans = sorted((s, e, name) for _, name, s, e in trace.host
                   if name not in skip)
    out: dict[str, float] = defaultdict(float)
    active: list = []                # spans begun before the current gap
    i = 0
    for gs, ge in gaps:
        while i < len(spans) and spans[i][0] < ge:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > gs]
        best, best_key = "(no host span)", (0.0, 0.0)
        for s, e, name in active:
            key = (min(e, ge) - max(s, gs), -(e - s))
            if key[0] > 0 and key > best_key:
                best, best_key = name, key
        out[best] += ge - gs
    return dict(out)


def _summed(per_chip_events, t0: float, t1: float) -> dict[str, float]:
    total: dict[str, float] = defaultdict(float)
    for events in per_chip_events:
        for name, s, e in _clipped(events, t0, t1):
            total[name] += e - s
    n = len(per_chip_events)
    return {k: v / n for k, v in total.items()}


def program_ns(trace: Trace, t0: float, t1: float) -> dict[str, float]:
    """Device nanoseconds per compiled program, averaged over the chips."""
    return _summed(trace.programs, t0, t1)


def _self_times(events):
    """(name, start, end) with the time of nested operations taken out:
    a loop's event spans the operations of its body, which have events of
    their own."""
    out, stack = [], []          # stack: [name, start, end, children_ns]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            out.append(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    out.extend(stack)
    return [(name, s, s + (e - s) - kids) for name, s, e, kids in out]


def op_ns(trace: Trace, t0: float, t1: float) -> dict[str, float]:
    """Device self nanoseconds per operation name, averaged over the
    chips."""
    return _summed([_self_times(_clipped(ops, t0, t1)) for ops in trace.ops],
                   t0, t1)


def op_events(trace: Trace, pattern: str, t0: float, t1: float,
              chip: int = 0) -> list[tuple[str, float, float]]:
    """The operations on ``chip`` whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return [ev for ev in _clipped(trace.ops[chip], t0, t1)
            if rx.search(ev[0])]
