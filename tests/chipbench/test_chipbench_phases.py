"""The readers of the program's phase histograms, on a registry filled
by hand."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench.harness import Record  # noqa: E402
from chipbench.manifest import reader  # noqa: E402
from repro import obs  # noqa: E402

PHASE_OF = {"dispatch_ms": "dispatch", "h2d_ms": "h2d", "body_ms": "body",
            "device_wait_ms": "wait", "d2h_ms": "d2h",
            "copy_back_ms": "copy_back"}
WINDOW = 512


def _record(kernel: str) -> Record:
    return Record("c", kernel, calls=WINDOW, window_s=10.0, timed_s=7.0)


def _fill(kernel: str, seconds: dict, calls: int = WINDOW + 1,
          warm_s: float = 5.0) -> None:
    """A kernel scope whose first (warm) call took ``warm_s`` in each
    phase and whose other calls alternate about ``seconds[phase]``; and
    a second scope of the same kernel with fewer calls, which the
    readers pass over."""
    for n in (calls, 3):
        scope = obs.metrics.unique_scope(f"kernel.{kernel}")
        for phase, s in seconds.items():
            h = scope.histogram(f"phase.{phase}_s")
            h.observe(warm_s)
            for i in range(1, n):
                h.observe(s * (0.5 if i % 2 else 1.5) if n < calls
                          else s * (0.9 if i % 2 else 1.1))


@pytest.mark.parametrize("metric", sorted(PHASE_OF))
def test_phase_reader_takes_the_median_without_the_warm_call(metric):
    kernel = f"phases_{metric}"
    seconds = {p: 0.001 * (i + 1) for i, p in enumerate(PHASE_OF.values())}
    _fill(kernel, seconds)
    phase = PHASE_OF[metric]
    # 512 calls after the warm one fill the window: half read 0.9 s, half
    # 1.1 s of the phase's time; the warm call's 5 s has left the window
    assert reader(metric)(_record(kernel)) == pytest.approx(
        1e3 * seconds[phase])


@pytest.mark.parametrize("metric", sorted(PHASE_OF))
def test_phase_reader_gives_none_without_a_kernel_scope(metric):
    assert reader(metric)(_record("phases_no_such_kernel")) is None
    # a scope of the kernel that holds no phase histograms (a program
    # that records none) gives nothing to read either
    obs.metrics.unique_scope("kernel.phases_bare").counter(
        "variants.jnp.calls").inc(3)
    assert reader(metric)(_record("phases_bare")) is None
