"""The held experts' ragged matmul's share of its roofline in the Granite
4.0-H cell (kernels layer, ``kernels/moe_experts.py``): the least time the
needed work -- gate, up and down projections over the rows routed here on
average, the held experts' weights and those rows moved once -- could
take on the chip, over the device time of the kernel's three calls a
layer."""

from bench.family_readers import kernel_roofline
from bench.models.granite_hybrid import EXPERT_LAUNCHES


def read(t):
    return kernel_roofline(t, "moe_experts", launches=EXPERT_LAUNCHES)
