"""Selective-scan (Mamba) as a chunked Pallas TPU kernel.

The GPU reference implementation is a warp-parallel prefix scan; the TPU
adaptation (DESIGN.md §2) is a CHUNKED recurrence: the sequence axis is
tiled into VMEM-resident chunks scanned by the sequential grid axis, with
the state carried in fp32 scratch. Inside a chunk the recurrence runs as
a fori_loop over timesteps; each step reads its row of x/dt straight
from the refs (``pl.ds``) and is a fully vectorized elementwise update
of the state, which is what the 8×128 VPU wants; cross-chunk
parallelism comes from the batch grid axis.

The state is kept transposed, ``(N, I)``, so a timestep's ``(1, I)`` row
of x/dt broadcasts over it without a relayout; B and C arrive transposed
to ``(B, N, L)`` for the same reason (their per-step ``(N, 1)`` column).

VMEM per step = 3·chunk·I (x, dt, y) + 2·N·chunk (B, C) + N·I state fp32
— ~1.6 MB at (chunk=128, I=1024, N=16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# block indices are int32 on the TPU; a bare ``0`` in an index map turns
# int64 when x64 is on, and Mosaic refuses to lower that
_ZERO = np.int32(0)


def _scan_kernel(x_ref, dt_ref, bt_ref, ct_ref, at_ref, dskip_ref, y_ref,
                 h_ref, *, chunk: int, acc_dtype):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    decay = -jnp.exp(at_ref[...].astype(acc_dtype))  # (N, I)
    dskip = dskip_ref[...].astype(acc_dtype)         # (1, I)
    bmat = bt_ref[0].astype(acc_dtype)               # (N, chunk)
    cmat = ct_ref[0].astype(acc_dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, bmat.shape, 1)

    def step(t, h):
        xt = x_ref[0, pl.ds(t, 1), :].astype(acc_dtype)     # (1, I)
        dtt = dt_ref[0, pl.ds(t, 1), :].astype(acc_dtype)   # (1, I)
        # column t of B/C: the TPU loads no dynamic lane offset, so the
        # column is picked by a masked lane reduction instead
        pick = lane == t
        bt = jnp.sum(bmat * pick, axis=1, keepdims=True)    # (N, 1)
        ct = jnp.sum(cmat * pick, axis=1, keepdims=True)
        h = jnp.exp(dtt * decay) * h + (dtt * xt) * bt      # (N, I)
        yt = jnp.sum(h * ct, axis=0, keepdims=True)         # (1, I)
        y_ref[0, pl.ds(t, 1), :] = (yt + dskip * xt).astype(y_ref.dtype)
        return h

    # int32 bounds keep the step index int32 when x64 is on
    h_ref[...] = jax.lax.fori_loop(jnp.int32(0), jnp.int32(chunk), step,
                                   h_ref[...])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba_scan(x, dt, Bm, Cm, a, d_skip, *, chunk: int = 128,
               interpret: bool = False):
    """x/dt: (B, L, I); Bm/Cm: (B, L, N); a: (I, N); d_skip: (I,).

    ``chunk`` must divide L and be a multiple of 8 (or L itself); the
    ops wrapper pads L to fit."""
    b, l, inner = x.shape
    n = Bm.shape[-1]
    chunk = min(chunk, l)
    assert l % chunk == 0, (l, chunk)
    # state carried in at least fp32; f64 inputs keep full precision
    acc_dtype = jnp.promote_types(x.dtype, jnp.float32)
    kern = functools.partial(_scan_kernel, chunk=chunk,
                             acc_dtype=acc_dtype)
    return pl.pallas_call(
        kern,
        grid=(b, l // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, inner), lambda i, j: (i, j, _ZERO)),
            pl.BlockSpec((1, chunk, inner), lambda i, j: (i, j, _ZERO)),
            pl.BlockSpec((1, n, chunk), lambda i, j: (i, _ZERO, j)),
            pl.BlockSpec((1, n, chunk), lambda i, j: (i, _ZERO, j)),
            pl.BlockSpec((n, inner), lambda i, j: (_ZERO, _ZERO)),
            pl.BlockSpec((1, inner), lambda i, j: (_ZERO, _ZERO)),
        ],
        out_specs=pl.BlockSpec((1, chunk, inner),
                               lambda i, j: (i, j, _ZERO)),
        out_shape=jax.ShapeDtypeStruct((b, l, inner), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, inner), acc_dtype)],
        interpret=interpret,
    )(x, dt, jnp.swapaxes(Bm, 1, 2), jnp.swapaxes(Cm, 1, 2), a.T,
      d_skip.reshape(1, -1))
