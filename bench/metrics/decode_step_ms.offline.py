"""Median device time of one execution of the engine's decode-step
program (model step layer, ``models/transformer.py`` ``decode_step``), in
the offline cell's traced window."""

from bench.readers import decode_step_ms


def read(t):
    return decode_step_ms(t)
