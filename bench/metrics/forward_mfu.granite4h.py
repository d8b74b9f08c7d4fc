"""The whole forward step's share of the chip's bf16 peak in the Granite
4.0-H cell (model step layer, ``models/transformer.py``): needed FLOPs of
the steps in the traced window -- projections, router, shared and held
experts, SSD recurrence, causal attention, unembedding -- over the window
times the peak.  Its own name because ``bench/work.py``, which
``forward_mfu`` reads through, dispatches on family."""

from bench.family_readers import forward_mfu


def read(t):
    return forward_mfu(t)
