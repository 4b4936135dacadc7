"""Runtime surface of the pallas backend (bound as ``__plk`` in twins).

The pattern matcher (:mod:`repro.core.patterns`) rewrites recognized
pfor unit bodies onto these three entry points; generated pallas twins
call them with plain numpy blocks and store the numpy result back into
the captured (possibly chunk-sliced) arrays. Each wrapper adapts the
matched shape onto the corresponding seed Pallas kernel:

* :func:`matmul` — blocked matmul (``kernels/matmul``), ragged shapes
  padded by the kernel's own dispatcher.
* :func:`attention_rows` — unscaled-softmax row attention onto the
  flash kernel (``kernels/flash_attention``): the kernel bakes in a
  ``1/sqrt(d)`` score scale, so queries are pre-multiplied by
  ``sqrt(d)`` to cancel it. Query rows are zero-padded to the query
  block; keys cannot be (zero keys would pollute the softmax), so the
  key block is a divisor of the key count that keeps the TPU's (8, 128)
  tiling rule, or the whole key range.
* :func:`scan_rows` — first-order linear recurrence onto the selective
  scan kernel (``kernels/mamba_scan``) via the identity mapping
  ``dt=1, B=C=1 (N=1), a=log(-log(c))`` which requires ``0<c<1``; an
  out-of-range coefficient raises, which the cluster counts as a
  lowering failure and degrades down the ``TaskSpec.alt`` chain.

Whether a kernel compiles or runs in Pallas *interpret* mode follows
the backend of the process that runs it
(:func:`repro.kernels.interpret_mode`): compiled on a TPU, interpreted
elsewhere, so CPU CI exercises the full routing path.
``REPRO_PALLAS_CHAOS=fail`` makes every entry point raise
(deterministic fallback-path tests).

This module enables jax x64 itself: generated chunk bodies compute in
the caller's (usually float64) dtype, and the serializer's x64 forcing
only covers jax-prefixed module globals, which ``__plk`` is not.
"""

from __future__ import annotations

import math
import os
from typing import Dict

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402  (after x64 so f64 survives)

from . import interpret_mode  # noqa: E402
from .flash_attention.flash_attention import flash_attention_bhsd  # noqa: E402
from .mamba_scan import ops as _mamba_ops  # noqa: E402
from .matmul import ops as _matmul_ops  # noqa: E402

_STATS: Dict[str, float] = {}


def _bump(key: str, val: float = 1) -> None:
    _STATS[key] = _STATS.get(key, 0) + val


def stats() -> Dict[str, float]:
    """Counters accumulated since the last :func:`take_stats`."""
    return dict(_STATS)


def take_stats() -> Dict[str, float]:
    """Drain the counters; the worker piggybacks them on chunk ``done``
    messages exactly like :func:`repro.distrib.accel.take_stats`."""
    out = dict(_STATS)
    _STATS.clear()
    return out


def reset() -> None:
    _STATS.clear()


def _chaos() -> None:
    if os.environ.get("REPRO_PALLAS_CHAOS") == "fail":
        raise RuntimeError("pallas-chaos")


def _count(interpret: bool) -> None:
    _bump("pallas_calls")
    if interpret:
        _bump("pallas_interpret_calls")


def _key_block(n: int, pref: int = 128) -> int:
    """Key block for ``n`` keys: the largest divisor of ``n`` up to
    ``pref`` that is a multiple of 8, else all ``n`` keys (a
    full-extent block always meets the TPU's tiling rule)."""
    for b in range(min(pref, n) // 8 * 8, 0, -8):
        if n % b == 0:
            return b
    return n


def matmul(a, b):
    """``a @ b`` through the blocked Pallas matmul kernel."""
    _chaos()
    interpret = interpret_mode()
    _count(interpret)
    out = _matmul_ops.matmul(jnp.asarray(a), jnp.asarray(b),
                             force_pallas=True, interpret=interpret)
    return np.asarray(out)


def attention_rows(q, k, v):
    """Unscaled-softmax attention for a block of query rows.

    ``out[r, j] = sum_t exp(q[r]·k[t]) v[t, j] / sum_t exp(q[r]·k[t])``
    with q ``(R, D)``, k ``(T, D)``, v ``(T, D)``.
    """
    _chaos()
    interpret = interpret_mode()
    _count(interpret)
    return np.asarray(attention_block(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), interpret=interpret))


def attention_block(q, k, v, *, interpret: bool):
    """:func:`attention_rows` on jax arrays (what compiles)."""
    rows, d = q.shape
    # cancel the kernel's baked-in 1/sqrt(d) score scale
    qs = q * jnp.asarray(math.sqrt(d), q.dtype)
    # query rows are independent: pad them to a whole number of blocks
    bq = min(128, -(-rows // 8) * 8)
    qs = jnp.pad(qs, ((0, (-rows) % bq), (0, 0)))
    out = flash_attention_bhsd(
        qs[None], k[None], v[None], causal=False, window=0, softcap=0.0,
        bq=bq, bk=_key_block(k.shape[0]), interpret=interpret)
    return out[0, :rows]


def scan_rows(x_rows, c):
    """First-order recurrence ``h_t = c*h_{t-1} + x[r, t]`` per row,
    ``h_{-1} = 0``, through the selective-scan kernel."""
    _chaos()
    c = float(c)
    if not 0.0 < c < 1.0:
        raise ValueError(
            f"pallas-lowering-infeasible: scan decay coefficient {c!r} "
            f"outside (0, 1) (a = log(-log(c)) undefined)")
    interpret = interpret_mode()
    _count(interpret)
    return np.asarray(scan_block(jnp.asarray(x_rows), c,
                                 interpret=interpret))


def scan_block(x_rows, c: float, *, interpret: bool):
    """:func:`scan_rows` on a jax array, ``0 < c < 1`` (what compiles)."""
    rows, length = x_rows.shape
    dtype = x_rows.dtype
    # identity mapping: B=1 batch, I=rows channels, N=1 state; with
    # dt=1 and B=C=1 the recurrence collapses to h = exp(-exp(a))*h + x
    # and a = log(-log(c)) makes exp(-exp(a)) == c exactly
    x = x_rows.T[None]                               # (1, L, R)
    dt = jnp.ones((1, length, rows), dtype)
    ones_n = jnp.ones((1, length, 1), dtype)
    a = jnp.full((rows, 1), math.log(-math.log(c)), dtype)
    d_skip = jnp.zeros((rows,), dtype)
    y = _mamba_ops.mamba_scan(x, dt, ones_n, ones_n, a, d_skip,
                              force_pallas=True, interpret=interpret)
    return y[0].T                                    # (R, L)
