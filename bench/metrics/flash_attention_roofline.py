"""Flash attention's share of its roofline in the forward cells
(kernels layer, ``kernels/flash_attention.py``): the least time the
needed work could take on the chip over the kernel's device time."""

from bench.readers import kernel_roofline


def read(t):
    return kernel_roofline(t, "flash_attention")
