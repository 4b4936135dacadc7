"""Public selective-scan wrapper with backend dispatch."""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from .. import interpret_mode
from .mamba_scan import mamba_scan as _kernel
from .ref import mamba_scan_ref


def scan_chunk(length: int, chunk: int = 128) -> int:
    """Sequence block for a scan of ``length`` steps: ``chunk`` (a
    multiple of 128) for long sequences, else the whole sequence rounded
    up to a multiple of 8 — both keep the TPU's (8, 128) block rule."""
    return chunk if length > chunk else -(-length // 8) * 8


def mamba_scan(x, dt, Bm, Cm, a, d_skip, *, chunk: int = 128,
               force_pallas: bool = False,
               interpret: Optional[bool] = None):
    if interpret_mode() and not force_pallas:
        return mamba_scan_ref(x, dt, Bm, Cm, a, d_skip)
    if interpret is None:   # tests pass it; else the process decides
        interpret = interpret_mode()
    l = x.shape[1]
    c = scan_chunk(l, chunk)
    pad = (-l) % c
    if pad:
        # the recurrence is causal: zero steps appended at the end never
        # reach an earlier output, and their rows are cut off below
        widths = ((0, 0), (0, pad), (0, 0))
        x, dt, Bm, Cm = (jnp.pad(v, widths) for v in (x, dt, Bm, Cm))
    y = _kernel(x, dt, Bm, Cm, a, d_skip, chunk=c, interpret=interpret)
    return y[:, :l]
