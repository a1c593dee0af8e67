"""The fill kernel's share of its roofline: the least time the chip could
take for the calls in the trace (the larger of operations over the peak
rate of the unit that does them and bytes over the HBM peak, from
kernels/fill.py and peaks.json) over the time they took."""

from harness import roofline


def read(inp):
    return roofline.share(inp, "fill")
