"""Seconds of chunked prefill in the run, the sum of its
``prefill.chunk`` spans (each chunk timed to its end): the part of
set-up that admits the sessions."""

from chipbench.metrics._ticks import histogram


def read(rec):
    h = histogram("chunk")
    return h.total if h is not None and h.count else None
