"""Share of the offline cell's traced window in which the device sat idle
while the engine's host sampled tokens and copied them back (serving
engine layer, the ``engine.sample`` spans of ``serving/engine.py``)."""

from bench.program_spans import of_run, sample_idle_share


def read(t):
    return sample_idle_share(t, of_run(t))
