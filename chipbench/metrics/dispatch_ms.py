"""Median time of the dispatcher's decision, in ms: binding the
arguments, the call signature, the specialization and bucket lookups
and ``select()``, up to the chosen variant (``kernel.dispatch``)."""

from chipbench.metrics._phases import median_ms


def read(rec):
    return median_ms(rec, "dispatch")
