"""jit'd public wrapper for the blocked matmul kernel.

Dispatch is a profitability condition (paper §4.1): the Pallas kernel is
selected on TPU backends for MXU-aligned shapes; otherwise the jnp oracle
(which XLA lowers natively) runs. Padding handles ragged shapes.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from .. import interpret_mode
from .matmul import matmul as _matmul_kernel
from .ref import matmul_ref


def _pad_to(x, mult0, mult1):
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def matmul(x, y, *, bm: int = 128, bn: int = 128, bk: int = 512,
           force_pallas: bool = False,
           interpret: Optional[bool] = None):
    """Matmul with kernel dispatch. On non-TPU backends the reference
    path runs unless ``force_pallas`` (tests use interpret=True)."""
    if interpret_mode() and not force_pallas:
        return matmul_ref(x, y)
    if interpret is None:   # tests pass it; else the process decides
        interpret = interpret_mode()
    m, k = x.shape
    _, n = y.shape
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, k)
    xp = _pad_to(x, bm_, bk_)
    yp = _pad_to(y, bk_, bn_)
    out = _matmul_kernel(xp, yp, bm=bm_, bn=bn_, bk=bk_,
                         interpret=interpret)
    return out[:m, :n]
