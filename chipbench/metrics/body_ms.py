"""Median time of the variant's own function, in ms: for the jnp
variant, issuing its eager jnp operations (``kernel.body``), while the
host→device copies run on."""

from chipbench.metrics._phases import median_ms


def read(rec):
    return median_ms(rec, "body")
