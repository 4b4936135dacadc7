"""The serving engine's own timing of its ticks and prefill chunks, for
the readers beside this file.

``ServeEngine`` keeps, in its ``serve#N`` scope of the process's
``repro.obs.metrics``, a histogram ``phase.<phase>_s`` of each tick
phase's seconds (``admit``, ``issue``, ``wait``, ``d2h``, ``book``; the
last 512 ticks) and ``phase.chunk_s`` of each prefill chunk's. A
program without them gives nothing to read.
"""

from statistics import median

TICK_PHASES = ("admit", "issue", "wait", "d2h", "book")


def _scope():
    """The prefix ``serve#N`` of the engine that ran the most ticks in
    this process, or None."""
    from repro import obs

    best, most = None, 0
    for name in obs.metrics.names():
        if name.startswith("serve#") and name.endswith(".phase.wait_s"):
            count = obs.metrics.get(name).count
            if count > most:
                best, most = name[:-len(".phase.wait_s")], count
    return best


def histogram(phase: str):
    from repro import obs

    prefix = _scope()
    return obs.metrics.get(f"{prefix}.phase.{phase}_s") if prefix else None


def median_ms(phase: str):
    """The median of the phase's last observations, in milliseconds."""
    h = histogram(phase)
    window = h.recent() if h is not None else None
    return 1e3 * median(window) if window else None
