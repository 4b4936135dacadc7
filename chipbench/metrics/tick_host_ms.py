"""The host's share of a decode tick, in ms: the sum of the medians of
the tick's other phases (``tick.admit``, ``tick.issue``, ``tick.d2h``,
``tick.book``): admission, building and dispatching the step, the copy
of its tokens and counts home, and the bookkeeping."""

from chipbench.metrics._ticks import median_ms


def read(rec):
    parts = [median_ms(p) for p in ("admit", "issue", "d2h", "book")]
    return None if None in parts else sum(parts)
