"""The SSD chunked scan's share of its roofline in the Granite 4.0-H cell
(kernels layer, ``kernels/ssd_scan.py``): the least time the needed work,
``work.ssd_scan`` at the cell's 128 heads, could take on the chip over the
kernel's device time.  Its own name because ``bench/work.py``, which
``ssd_scan_roofline`` reads through, dispatches on family."""

from bench.family_readers import kernel_roofline


def read(t):
    return kernel_roofline(t, "ssd_scan")
