"""Median time of copying the outputs into the caller's arrays on the
host, in ms (``kernel.copy_back``)."""

from chipbench.metrics._phases import median_ms


def read(rec):
    return median_ms(rec, "copy_back")
