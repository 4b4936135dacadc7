"""Median time of issuing the host→device copies of the call's NumPy
operands, in ms (``kernel.h2d``). The copies run on past it: their
time lands in ``body_ms`` and ``device_wait_ms``."""

from chipbench.metrics._phases import median_ms


def read(rec):
    return median_ms(rec, "h2d")
