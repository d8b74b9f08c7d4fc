"""The SSD chunked scan's share of its roofline in the forward cells
(kernels layer, ``kernels/ssd_scan.py``): the least time the needed work
could take on the chip over the kernel's device time."""

from bench.readers import kernel_roofline


def read(t):
    return kernel_roofline(t, "ssd_scan")
