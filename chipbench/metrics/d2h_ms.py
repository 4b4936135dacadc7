"""Median time of the device→host copy of the call's outputs, in ms
(``kernel.d2h``)."""

from chipbench.metrics._phases import median_ms


def read(rec):
    return median_ms(rec, "d2h")
