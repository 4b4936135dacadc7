"""The program's own timing of each phase of a compiled kernel's call,
for the readers beside this file.

``CompiledKernel`` keeps, in its ``kernel.<name>#N`` scope of the
process's ``repro.obs.metrics``, a histogram ``phase.<phase>_s`` of each
phase's seconds (the last 512 calls). A program without them gives
nothing to read.
"""

from statistics import median


def _scope(rec):
    """The prefix ``kernel.<rec.kernel>#N`` whose kernel made the most
    calls in this process, or None."""
    from repro import obs

    best, most = None, 0
    head = f"kernel.{rec.kernel}#"
    for name in obs.metrics.names():
        if name.startswith(head) and name.endswith(".phase.body_s"):
            calls = obs.metrics.get(name).count
            if calls > most:
                best, most = name[:-len(".phase.body_s")], calls
    return best


def median_ms(rec, phase: str):
    """The median of the phase's last observations, in milliseconds."""
    from repro import obs

    prefix = _scope(rec)
    h = obs.metrics.get(f"{prefix}.phase.{phase}_s") if prefix else None
    window = h.recent() if h is not None else None
    return 1e3 * median(window) if window else None
