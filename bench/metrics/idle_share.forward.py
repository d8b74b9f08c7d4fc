"""Share of the traced forward window in which no operation ran on the
device (device layer)."""

from bench.readers import idle_share


def read(t):
    return idle_share(t)
