"""Blocked MXU matmul: the accelerator variant of the compiler's raised
``np.dot`` (the paper's NumPy→CuPy conversion, re-targeted at TPU).

Grid (M/bm, N/bn, K/bk); K is the innermost (sequential) axis so the fp32
VMEM accumulator carries across K steps. Block sizes default to 128×128
tiles (MXU-aligned: the systolic array is 128×128) with bk=512 to amortize
HBM→VMEM transfers; VMEM footprint = bm·bk + bk·bn + 2·bm·bn fp32 ≤ ~1.6MB
at defaults, well under the 128 MiB v5e VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import dot_precision


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref, *, k_steps: int,
                   acc_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], y_ref[...],
                            precision=dot_precision(x_ref.dtype),
                            preferred_element_type=acc_dtype)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk",
                                             "interpret"))
def matmul(x, y, *, bm: int = 128, bn: int = 128, bk: int = 512,
           interpret: bool = False):
    """x: (M, K), y: (K, N) → (M, N). Shapes must tile evenly (ops.py
    pads otherwise)."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (m, n, k, bm, bn, bk)
    k_steps = k // bk
    # accumulate in at least fp32; f64 inputs keep full precision
    acc_dtype = jnp.promote_types(x.dtype, jnp.float32)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps,
                          acc_dtype=acc_dtype),
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )(x, y)
