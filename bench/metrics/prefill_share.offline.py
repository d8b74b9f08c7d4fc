"""Share of the device's busy time in the engine's prefill program
(serving engine layer, ``serving/engine.py`` ``prefill_chunk_step``), in the
offline cell's traced window."""

from bench.readers import PREFILL_PROGRAM, program_share


def read(t):
    return program_share(t, PREFILL_PROGRAM)
