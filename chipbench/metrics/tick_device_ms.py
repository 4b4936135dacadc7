"""Median time a decode tick waits for the device, in ms
(``tick.wait``): from the step's dispatch until its tokens and MoE
counts are ready, which is the device's work on the tick."""

from chipbench.metrics._ticks import median_ms


def read(rec):
    return median_ms("wait")
