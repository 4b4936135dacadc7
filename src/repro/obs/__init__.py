"""repro.obs — distributed tracing + unified metrics plane.

One import point for the whole instrument panel:

    from repro import obs

    obs.enable()                        # or REPRO_TRACE=1 / optimize(trace=True)
    with obs.span("gather", cat="pfor", round=3):
        ...
    tok = obs.begin("chunk_inflight", cat="pfor", tid=...)  # cross-thread
    obs.end(tok)                        # any thread, idempotent

    obs.metrics.scope("cluster0").inc("blob_hits")
    phases = obs.Phases(obs.metrics.scope("kernel.gemm#0"), "gemm",
                        ("dispatch", "h2d"), counts_bytes=("h2d",))
    clock = phases.start("dispatch")    # histograms phase.<name>_s,
    clock.next("h2d")                   # always
    clock.moved(n)                      # counter h2d_bytes
    clock.stop()
    obs.export_chrome_trace("trace.json")     # Perfetto-loadable
    # python -m repro.obs.summarize trace.json  → text breakdown

Tracing is **off by default**: ``span``/``begin``/``end`` cost one flag
check when dark (a shared no-op context manager, no allocation). The
metrics registry is always live — it is the single backing store behind
``ClusterRuntime.stats()``, ``CompiledKernel.stats()`` and
``ServeEngine.telemetry()`` — because counters are how those surfaces
already work; only the *timeline* recording is gated. A
:class:`Phases` clock also lands in a JAX profiler trace while one records.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Any, Dict, Optional

from . import chrome as _chrome
from .metrics import MetricAttr, registry as metrics  # noqa: F401
from .metrics import record as _record_metrics
from .spans import WORKER_TID_BASE, SpanRecorder, SpanToken  # noqa: F401

__all__ = ["enabled", "enable", "disable", "span", "begin", "end",
           "Phases", "PhaseClock", "recorder", "export_chrome_trace",
           "metrics", "MetricAttr", "worker_tid", "WORKER_TID_BASE"]

_enabled = os.environ.get("REPRO_TRACE", "") not in ("", "0", "false")
_recorder = SpanRecorder()


def enabled() -> bool:
    return _enabled


def enable(capacity: Optional[int] = None) -> None:
    """Turn span recording on (idempotent). ``capacity`` resizes the
    ring buffer (only when it changes — enabling mid-run never drops
    what was already recorded)."""
    global _enabled, _recorder
    if capacity is not None and capacity != _recorder.capacity:
        _recorder = SpanRecorder(capacity)
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def recorder() -> SpanRecorder:
    return _recorder


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Span:
    __slots__ = ("name", "cat", "args", "t0")

    def __init__(self, name: str, cat: str, args: Optional[Dict]):
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = 0.0

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        _recorder.record(self.name, self.cat, self.t0,
                         time.perf_counter(), args=self.args)
        return False


def span(name: str, cat: str = "app", **args: Any):
    """Context manager recording one span on the current thread.
    A no-op singleton when tracing is off."""
    if not _enabled:
        return _NULL
    return _Span(name, cat, args or None)


def _profiler_annotation():
    """JAX's ``TraceAnnotation`` while a profiler session records, else
    None. jax is looked up, never imported: obs must not pull it in."""
    prof = sys.modules.get("jax.profiler")
    if prof is None:
        return None
    ann = prof.TraceAnnotation
    return ann if ann.is_enabled() else None


class Phases:
    """The timed phases of a compiled kernel's calls, bound once per
    kernel to the histograms ``phase.<name>_s`` of its metrics scope and,
    for each name in ``counts_bytes``, to the counter ``<name>_bytes``.
    ``prefix`` names the events and the ring's category (``kernel`` by
    default; the serving engine's ticks use ``tick``).

    ``clock = phases.start("dispatch")`` begins a call's first phase,
    ``clock.next(name)`` ends the running phase and begins the next, and
    ``clock.stop()`` ends the last one and records the call: always each
    phase's seconds into its histogram and the bytes given to
    ``clock.moved`` into its counter, under one lock; with tracing on
    also a ring span per phase (``cat=prefix``, args ``kernel`` and
    ``bytes``). While a JAX profiler session records (looked up once,
    at ``start``), each phase is also the host event ``<prefix>.<name>``
    in that trace, on the clock of the device ops. The event name is
    fixed; per-call values go only in the ring's args. A call that
    raises before ``stop`` records nothing."""

    __slots__ = ("kernel", "hists", "counters", "events", "cat")

    def __init__(self, scope, kernel: str, names, counts_bytes=(),
                 prefix: str = "kernel"):
        self.kernel = kernel
        self.hists = {n: scope.histogram(f"phase.{n}_s") for n in names}
        self.counters = {n: scope.counter(f"{n}_bytes")
                         for n in counts_bytes}
        self.events = {n: f"{prefix}.{n}" for n in names}
        self.cat = prefix

    def start(self, name: Optional[str] = None) -> "PhaseClock":
        clock = PhaseClock(self, _profiler_annotation())
        if name is not None:
            clock.next(name)
        return clock


class PhaseClock:
    """One call's run through its :class:`Phases`: the phases follow
    one another with no gap, so they add up to the whole call."""

    __slots__ = ("phases", "marks", "_ann", "_open")

    def __init__(self, phases: Phases, ann):
        self.phases = phases
        self.marks: list = []        # [name, start, bytes] per phase
        self._ann = ann
        self._open = None

    def next(self, name: str) -> None:
        t = time.perf_counter()
        if self._ann is not None:
            self._close()
            self._open = self._ann(self.phases.events[name])
            self._open.__enter__()
        self.marks.append([name, t, 0])

    def moved(self, nbytes: int) -> None:
        """Count ``nbytes`` as moved by the running phase."""
        self.marks[-1][2] += nbytes

    def _close(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def stop(self, after: Optional[str] = None) -> float:
        """End the last phase and record the call. Returns the seconds
        of the phases that follow the phase ``after`` (of all of them,
        by default)."""
        t_end = time.perf_counter()
        self._close()
        ph, marks = self.phases, self.marks
        ends = [m[1] for m in marks[1:]] + [t_end]
        _record_metrics(
            [(ph.hists[name], t1 - t0)
             for (name, t0, _), t1 in zip(marks, ends)],
            [(ph.counters[name], n) for name, _, n in marks
             if name in ph.counters])
        if _enabled:
            for (name, t0, n), t1 in zip(marks, ends):
                args = {"kernel": ph.kernel, "bytes": n} if n else \
                    {"kernel": ph.kernel}
                _recorder.record(ph.events[name], ph.cat, t0, t1,
                                 args=args)
        if after is None:
            return t_end - marks[0][1]
        return t_end - marks[[m[0] for m in marks].index(after) + 1][1]


def begin(name: str, cat: str = "app",
          **args: Any) -> Optional[SpanToken]:
    """Start a cross-thread span; returns a token (or None when
    tracing is off) that any thread passes to :func:`end`."""
    if not _enabled:
        return None
    return _recorder.begin(name, cat, args or None)


def end(token: Optional[SpanToken],
        **extra: Any) -> None:
    if token is None:
        return
    _recorder.end(token, extra or None)


def worker_tid(wid: int) -> int:
    """Track id for worker ``wid`` on its node (head threads keep the
    small tids)."""
    return WORKER_TID_BASE + wid


def export_chrome_trace(path: str,
                        extra_meta: Optional[Dict[str, Any]] = None
                        ) -> str:
    """Write the recorded spans as Perfetto/chrome://tracing JSON."""
    return _chrome.export_chrome_trace(_recorder, path, extra_meta)
