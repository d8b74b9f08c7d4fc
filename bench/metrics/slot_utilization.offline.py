"""Useful slot-steps over the slot-steps the engine executed in the
offline cell's traced window (serving engine layer, the
``engine.decode`` and ``engine.prefill_chunk`` spans of
``serving/engine.py``): a prefill lane runs the whole decode step for
one slot."""

from bench.program_spans import of_run, slot_utilization


def read(t):
    return slot_utilization(of_run(t), t.t0, t.t1)
