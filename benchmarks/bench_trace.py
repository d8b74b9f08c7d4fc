"""Tracing-overhead benchmark on the CPU: the span primitives' own cost.

Measures:

  * **span record cost** -- an enter/exit ``trace_span`` pair with a live
    tracer must cost <= max(2us, 2x a measured span floor): the floor is
    the irreducible interpreter cost of the same design (a factory call
    building an attributed slotted object, a thread-local nesting stack,
    two clock reads, a bounded ring append, a bucketed histogram add)
    with none of the tracer's extras, so throttled runners scale the
    budget the same way they scale the measurement;
  * **disabled span cost** -- ``trace_span`` with no tracer (one global
    load + ``is None``) and the ``@traced`` passthrough, reported;
  * **ledger append** -- one JSONL line (json.dumps + write + flush),
    reported.

Writes ``BENCH_trace.json`` (schema ``version: 1``) next to this file.

    PYTHONPATH=src python benchmarks/bench_trace.py            # full run
    PYTHONPATH=src python benchmarks/bench_trace.py --smoke    # CI gate
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

from repro.trace import Ledger, Tracer, set_tracer, trace_span

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_PATH = os.path.join(HERE, "BENCH_trace.json")

SPAN_RECORD_BAR_S = 2e-6     # absolute enabled enter/exit budget per span
SPAN_FLOOR_MULT = 2.0        # ... scaled up to this x the measured span
                             # floor on boxes too slow for the absolute bar

def _time_best(fn, reps=7):
    """Best-of-``reps`` wall time with the collector paused (the timeit
    convention)."""
    import gc
    best, out = float("inf"), None
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            best = min(best, time.perf_counter() - t0)
    finally:
        if was_enabled:
            gc.enable()
    return out, best


def _span_floor(n: int) -> float:
    """Per-iteration cost of the irreducible span structure: what *any*
    implementation of the span design must pay in the interpreter -- a
    factory call building an attributed slotted object, a thread-local
    nesting stack push/pop, two monotonic clock reads, a bounded ring
    append and a bucketed histogram add -- with none of the tracer's
    extras (depth/identity capture, shard registration, error attrs, the
    ledger gate).  The gate budgets the real span against a multiple of
    this so a throttled runner scales budget and measurement together."""
    import bisect
    import threading
    from collections import deque
    ring = deque(maxlen=256)
    hist = {"bench": [[0] * 9, 0, 0]}
    clock = time.monotonic_ns
    bounds = tuple(int(b * 1e9)
                   for b in (1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1, 1.0, 10.0))
    bl = bisect.bisect_left
    local = threading.local()
    local.stack = []

    class CM:
        __slots__ = ("name", "attrs", "t0", "t1")

        def __init__(self, name, attrs):
            self.name = name
            self.attrs = attrs

        def __enter__(self):
            local.stack.append(self)
            self.t0 = clock()
            return self

        def __exit__(self, *exc):
            t1 = self.t1 = clock()
            stack = local.stack
            if stack and stack[-1] is self:
                stack.pop()
            ring.append(self)
            row = hist["bench"]
            d = t1 - self.t0
            row[0][bl(bounds, d)] += 1
            row[1] += d
            row[2] += 1
            return False

    def make(name, **attrs):
        return CM(name, attrs)

    def floor_loop():
        for _ in range(n):
            with make("bench", k=1):
                pass
    _, s = _time_best(floor_loop)
    return s / n


def bench_span_cost(n: int = 20000) -> dict:
    """Per-span primitive costs: enabled record, disabled call, ledger."""
    tracer = Tracer(capacity=256)   # eviction in steady state, like serving
    tracer.install()
    try:
        def spans_enabled():
            for _ in range(n):
                with trace_span("bench", k=1):
                    pass
        _, on_s = _time_best(spans_enabled)
    finally:
        tracer.uninstall()

    set_tracer(None)

    def spans_disabled():
        for _ in range(n):
            with trace_span("bench", k=1):
                pass
    _, off_s = _time_best(spans_disabled)

    def null_loop():
        for _ in range(n):
            pass
    _, base_s = _time_best(null_loop)

    floor_s = _span_floor(n)

    with tempfile.TemporaryDirectory() as td:
        led = Ledger(os.path.join(td, "bench.jsonl"))
        event = {"type": "choice", "kernel": "matmul_b16",
                 "D": {"m": 1024, "n": 1024, "k": 1024},
                 "config": {"bm": 128, "bn": 512, "bk": 512},
                 "source": "driver", "predicted_s": 1e-3,
                 "t_ns": 123456789}
        m = 2000

        def appends():
            for _ in range(m):
                led.append(event)
        _, led_s = _time_best(appends, reps=3)
        led.close()

    return {
        "span_record_s": max(on_s - base_s, 0.0) / n,
        "span_disabled_s": max(off_s - base_s, 0.0) / n,
        "span_floor_s": floor_s,
        "ledger_append_s": led_s / m,
        "n_spans": n,
    }


def run() -> dict:
    return {
        "version": 1,
        "span_record_bar_s": SPAN_RECORD_BAR_S,
        "span_floor_mult": SPAN_FLOOR_MULT,
        "span": bench_span_cost(),
    }


def main(argv=None) -> list[str]:
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    report = run()
    if not smoke:
        with open(OUT_PATH, "w") as f:
            json.dump(report, f, indent=2)
    s = report["span"]
    lines = [
        f"trace/span_record,{s['span_record_s'] * 1e6:.3f},"
        f"enabled enter/exit incl. ring+histogram "
        f"(floor {s['span_floor_s'] * 1e6:.3f}us)",
        f"trace/span_disabled,{s['span_disabled_s'] * 1e6:.4f},"
        f"no-tracer trace_span call",
        f"trace/ledger_append,{s['ledger_append_s'] * 1e6:.2f},"
        f"one JSONL line (dumps+write+flush)",
    ]

    failures = []
    span_budget = max(SPAN_RECORD_BAR_S, SPAN_FLOOR_MULT * s["span_floor_s"])
    if s["span_record_s"] > span_budget:
        failures.append(
            f"enabled span record {s['span_record_s'] * 1e9:.0f}ns > max("
            f"{SPAN_RECORD_BAR_S * 1e9:.0f}ns, {SPAN_FLOOR_MULT:.0f}x "
            f"{s['span_floor_s'] * 1e9:.0f}ns span floor)")
    if failures:
        lines.append(f"trace/FAIL,0,{'; '.join(failures)}")
        if smoke:
            for ln in lines:
                print(ln)
            sys.exit(1)
    return lines


if __name__ == "__main__":
    for ln in main():
        print(ln)
