"""The program's own spans in a traced window, and the serving-engine
metrics read from them.

While a profiler records, ``repro.trace`` writes each span of the program
into the trace's host plane as an annotation, with the span's attributes
as the event's stats, on the same clock as the device's operations.
``load`` keeps the spans named ``engine.*`` (the serving engine's
scheduler, ``serving/engine.py``) with their attributes; the readers
intersect them with the device intervals of ``trace_reduce``.  A
``Spans`` round-trips through JSON, so the readers are tested on a
recorded excerpt without the chip.  A program that writes no such spans
gives ``None``.
"""

from __future__ import annotations

import gzip
import json
import os
from dataclasses import dataclass, field

from bench import harness
from bench import trace_reduce as tr

__all__ = ["Spans", "load", "of_run", "sample_idle_share",
           "slot_utilization"]

PREFIX = "engine."
DECODE = "engine.decode"
PREFILL_CHUNK = "engine.prefill_chunk"
SAMPLE = "engine.sample"


@dataclass
class Spans:
    # [thread, name, start_ns, end_ns, {attribute: value}], by start
    spans: list = field(default_factory=list)

    def to_json(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"spans": self.spans}, f)

    @classmethod
    def from_json(cls, path: str) -> "Spans":
        with gzip.open(path, "rt") as f:
            return cls(**json.load(f))

    def named(self, name: str, t0: float, t1: float):
        """(start, end) of each span called ``name``, clipped to the
        window; those outside it are left out."""
        for _, n, s, e, _ in self.spans:
            if n == name and e > t0 and s < t1:
                yield max(s, t0), min(e, t1)

    def touches(self, t0: float, t1: float) -> bool:
        return any(e > t0 and s < t1 for _, _, s, e, _ in self.spans)


def load(path: str) -> Spans:
    """The host-plane events of the ``.xplane.pb`` at ``path`` whose name
    starts with ``PREFIX``, with their stats."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            out.extend([line.name, e.name, e.start_ns, e.end_ns,
                        dict(e.stats)]
                       for e in line.events if e.name.startswith(PREFIX))
    out.sort(key=lambda sp: (sp[2], -sp[3]))
    return Spans(out)


_LOADED: dict = {}


def of_run(t) -> Spans:
    """The program spans of the traced run that ``t`` reduces, loaded
    once for all the metrics that read them."""
    path = tr.find_xplane(os.path.join(harness.TRACE_DIR, t.cell.name))
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = load(path)
    return _LOADED[key]


def _overlap_ns(a, b) -> float:
    """Summed length of the intersection of two sorted lists of disjoint
    (start, end) intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def sample_idle_share(t, spans: Spans) -> float | None:
    """Device-idle time of the window inside ``engine.sample`` spans, over
    the window, in %: the device waiting while the host samples tokens
    and copies them back."""
    if not spans.touches(t.t0, t.t1):
        return None
    sample = tr.union_intervals(spans.named(SAMPLE, t.t0, t.t1))
    idle = tr.idle_gaps(t.trace, t.t0, t.t1)
    return 100.0 * _overlap_ns(idle, sample) / t.window_ns


def slot_utilization(spans: Spans, t0: float, t1: float) -> float | None:
    """Useful slot-steps over executed slot-steps, in %, over the engine
    spans that begin in the window.  A decode round executes ``slots``
    slot-steps of which ``active`` are useful; a prefill dispatch runs
    ``lanes`` whole decode steps, each useful to one slot."""
    useful = executed = 0
    for _, name, s, _, a in spans.spans:
        if not t0 <= s < t1:
            continue
        if name == DECODE:
            useful += a["active"]
            executed += a["slots"]
        elif name == PREFILL_CHUNK:
            useful += a["lanes"]
            executed += a["slots"] * a["lanes"]
    if not executed:
        return None
    return 100.0 * useful / executed

