"""Median time the call waits for the variant's outputs, in ms
(``kernel.wait``): the rest of the host→device copies, which run on
past ``kernel.h2d``, and the device's work that follows them."""

from chipbench.metrics._phases import median_ms


def read(rec):
    return median_ms(rec, "wait")
