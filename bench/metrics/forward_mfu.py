"""The whole forward step's share of the chip's bf16 peak (model step
layer, ``models/transformer.py``): needed FLOPs of the steps in the
traced window over the window times the peak."""

from bench.readers import forward_mfu


def read(t):
    return forward_mfu(t)
