"""Device-side acceleration for jnp twin chunk bodies.

``pfor_jit`` is the fast path stamped into every accelerator-feasible
pfor twin body: instead of dispatching one eager jnp op stream per pfor
iteration, the twin hands its per-iteration function here and we

  * vmap it over a pow2-bucketed iteration index (the profiler's bucket
    tiers, via :func:`repro.core.cost.pow2_bucket`), so a serving loop
    hits the same compiled executable on call 2 even when
    capability-proportional chunking jitters the chunk size;
  * jit-compile once per (iteration code, baked scalars, bucket, array
    signature) and cache the executable process-wide, with recompile /
    hit / fallback telemetry;
  * keep ``remember()``-ed host arrays (worker blob cells and cached
    chunk rows) device-resident between calls instead of re-staging
    through ``asarray`` every round;
  * scatter only the real rows ``[lo, hi)`` back into the captured
    numpy arrays, so the worker's sparse-diff gather sees exactly the
    writes the eager body would have made.

``pfor_jit`` returns False whenever anything — missing jax, an
unbakeable closure cell, a trace or run failure — prevents the compiled
path; the twin then falls through to its eager per-iteration loop,
which is always correct. Failures are negatively cached so a shape that
cannot trace pays the probe once, not every round.
"""

from __future__ import annotations

import os
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["pfor_jit", "vmapped", "remember", "take_stats", "stats",
           "reset", "WIRE_STAT_KEYS"]

# Every counter key a worker may piggyback on a chunk "done" message —
# this module's jit/residency counters plus the pallas runtime's call
# counters (repro.kernels.api, drained the same way). The cluster's
# head-side aggregation derives its key set from this tuple, so adding
# a worker-side counter is a one-place change.
WIRE_STAT_KEYS = ("jit_hits", "jit_recompiles", "jit_fallbacks",
                  "jit_compile_s", "resident_hits", "resident_stages",
                  "resident_cells", "pallas_calls",
                  "pallas_interpret_calls")

# scalar types a closure cell may hold and still be baked into the
# compile-cache key (anything else → eager fallback)
_BAKEABLE = (int, float, complex, bool, str, bytes, type(None), np.generic)

_UNSET = object()
_JAX: Any = _UNSET

# (iter code, baked consts, bucket, array sig) → jitted callable, or
# None marking a combination that failed to trace/run (negative cache)
_COMPILED: Dict[tuple, Any] = {}

# (data ptr, shape, strides, dtype) → [host array (strong ref),
# {pad_rows: device array}]. Keyed by buffer layout, not object id,
# because chunk bodies see a *fresh* re-based view of the cached rows
# array every task — same buffer, new Python object. The strong ref
# pins the buffer so the pointer cannot be recycled by a different
# array while the entry lives; the LRU byte budget bounds how much
# host memory residency can pin.
_RESIDENT: "OrderedDict[tuple, List[Any]]" = OrderedDict()
_RESIDENT_BYTES = 0

_STATS: Dict[str, float] = {}


def _budget_bytes() -> int:
    try:
        mb = float(os.environ.get("REPRO_DISTRIB_RESIDENT_MB", "256"))
    except ValueError:
        mb = 256.0
    return int(mb * (1 << 20))


def _bump(key: str, val: float = 1) -> None:
    _STATS[key] = _STATS.get(key, 0) + val


def stats() -> Dict[str, float]:
    """Counters accumulated since the last :func:`take_stats`."""
    return dict(_STATS)


def take_stats() -> Dict[str, float]:
    """Drain and return the counter deltas ({} when nothing happened).

    The worker appends this to each chunk-task ``done`` message so the
    head can aggregate jit/residency telemetry fleet-wide.
    """
    out = dict(_STATS)
    _STATS.clear()
    return out


def reset() -> None:
    """Forget compiled executables, device residents, and counters
    (test isolation)."""
    global _RESIDENT_BYTES
    _COMPILED.clear()
    _RESIDENT.clear()
    _RESIDENT_BYTES = 0
    _STATS.clear()


def _jax():
    """jax with x64 enabled, or None when unavailable (cached)."""
    global _JAX
    if _JAX is not _UNSET:
        return _JAX
    try:
        import jax

        jax.config.update("jax_enable_x64", True)
        import jax.numpy  # noqa: F401  (force the submodule in)
    except Exception:
        _JAX = None
        return None
    _JAX = jax
    return jax


def remember(arr) -> None:
    """Register a host array as residency-eligible.

    Only arrays whose content is identity-stable between chunk tasks
    qualify: worker blob cells (replaced wholesale by ``update_blob``
    when they change) and cached chunk-row arrays (replaced when the
    head re-ships rows). The worker's snapshot/rollback in
    ``_chunk_updates`` guarantees the host copy is pristine again after
    every task, so a device copy staged once stays valid until the
    object itself is swapped out.
    """
    global _RESIDENT_BYTES
    if not isinstance(arr, np.ndarray) or arr.nbytes > _budget_bytes():
        return
    key = _reskey(arr)
    ent = _RESIDENT.get(key)
    if ent is not None:
        if ent[0] is arr:
            _RESIDENT.move_to_end(key)
            return
        # same layout, different object (pointer recycled after the old
        # entry's array died elsewhere): staged copies may be stale
        _RESIDENT_BYTES -= ent[0].nbytes
        del _RESIDENT[key]
    _RESIDENT[key] = [arr, {}]
    _RESIDENT_BYTES += arr.nbytes
    while _RESIDENT_BYTES > _budget_bytes() and len(_RESIDENT) > 1:
        _, old = _RESIDENT.popitem(last=False)
        _RESIDENT_BYTES -= old[0].nbytes


def _reskey(arr: np.ndarray) -> tuple:
    return (arr.__array_interface__["data"][0], arr.shape,
            arr.strides, str(arr.dtype))


def _stage(jax, jnp, raw: np.ndarray, pad_rows: int):
    dev = jax.device_put(raw)
    if pad_rows and raw.ndim and pad_rows > raw.shape[0]:
        widths = [(0, pad_rows - raw.shape[0])] + [(0, 0)] * (raw.ndim - 1)
        dev = jnp.pad(dev, widths)
    return dev


def _device_array(jax, jnp, host, sliced: bool, pad_rows: int):
    """Device handle for one captured array, through the residency
    cache when the underlying host buffer is registered."""
    raw = np.asarray(host)
    key = _reskey(raw)
    ent = _RESIDENT.get(key)
    if ent is not None:
        _RESIDENT.move_to_end(key)
        cache = ent[1]
        dev = cache.get(pad_rows)
        if dev is not None:
            _bump("resident_hits")
            return dev
        dev = _stage(jax, jnp, raw, pad_rows)
        if not cache:
            _bump("resident_cells")
        cache[pad_rows] = dev
        _bump("resident_stages")
        return dev
    _bump("resident_stages")
    return _stage(jax, jnp, raw, pad_rows)


def vmapped(iter_fn):
    """The program :func:`pfor_jit` compiles for one iteration function:
    ``iter_fn(g, offs, *arrays)`` vmapped over a vector of iteration
    indices ``g`` and jitted, called as ``(idx, offs, *arrays)``."""
    jax = _jax()

    def _run(idx, offs, *arrs):
        return jax.vmap(lambda g: iter_fn(g, offs, *arrs))(idx)

    return jax.jit(_run)


def pfor_jit(iter_fn, lo: int, hi: int, arrays: Sequence[Any],
             write_pos: Sequence[int]) -> bool:
    """Run ``iter_fn(g, offs, *arrays)`` for every g in [lo, hi) as one
    vmapped, jit-compiled call, scattering the returned rows back into
    ``arrays[p]`` for each p in ``write_pos``.

    Returns True when the compiled path ran (the caller's eager loop
    must be skipped), False when the caller must fall back to it.
    """
    if os.environ.get("REPRO_DISTRIB_JIT", "1").lower() in ("0", "false"):
        return False
    n = int(hi) - int(lo)
    if n <= 0:
        return True
    jax = _jax()
    if jax is None:
        _bump("jit_fallbacks")
        return False
    jnp = jax.numpy

    # closure cells become baked constants of the compiled executable —
    # they are part of the cache key, so they must be hashable scalars
    consts: List[Any] = []
    for cell in (iter_fn.__closure__ or ()):
        try:
            v = cell.cell_contents
        except ValueError:
            _bump("jit_fallbacks")
            return False
        if not isinstance(v, _BAKEABLE):
            _bump("jit_fallbacks")
            return False
        consts.append(v)

    from repro.core.cost import pow2_bucket

    bucket = int(pow2_bucket(n)[1])

    sig: List[tuple] = []
    offs: List[int] = []
    devs: List[Any] = []
    try:
        for a in arrays:
            sliced = hasattr(a, "_chunk_base")
            base = int(getattr(a, "_chunk_base", 0) or 0)
            raw = np.asarray(a)
            pad_rows = bucket if (sliced and raw.ndim) else 0
            shape = raw.shape[1:] if (sliced and raw.ndim) else raw.shape
            sig.append((str(raw.dtype), tuple(shape), sliced))
            offs.append(base)
            devs.append(_device_array(jax, jnp, raw, sliced, pad_rows))
    except Exception:
        _bump("jit_fallbacks")
        return False

    key = (iter_fn.__code__, tuple(consts), bucket, tuple(sig))
    fn = _COMPILED.get(key, _UNSET)
    if fn is None:  # known-bad: failed to trace/run before
        _bump("jit_fallbacks")
        return False

    # padded lanes re-run the last real iteration (clip) — their rows
    # are computed and discarded, so pad rows of the inputs never feed a
    # result that survives the scatter below
    idx = jnp.clip(jnp.arange(lo, lo + bucket), lo, hi - 1)
    offs_arr = jnp.asarray(np.asarray(offs, dtype=np.int64))

    if fn is _UNSET:
        # later cache hits reuse this closure, which is semantically
        # identical (same code + same baked cells)
        fn = vmapped(iter_fn)
        t0 = time.perf_counter()
        try:
            out = jax.block_until_ready(fn(idx, offs_arr, *devs))
        except Exception:
            _COMPILED[key] = None
            _bump("jit_fallbacks")
            return False
        _bump("jit_compile_s", time.perf_counter() - t0)
        _bump("jit_recompiles")
        _COMPILED[key] = fn
    else:
        try:
            out = jax.block_until_ready(fn(idx, offs_arr, *devs))
        except Exception:
            _bump("jit_fallbacks")
            return False
        _bump("jit_hits")

    outs = out if isinstance(out, tuple) else (out,)
    for pos, rows in zip(write_pos, outs):
        a = arrays[pos]
        off = int(getattr(a, "_chunk_base", 0) or 0)
        host = np.asarray(a)
        host[lo - off:hi - off] = np.asarray(rows[:n])
    return True
