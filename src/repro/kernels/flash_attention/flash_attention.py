"""Flash attention as a Pallas TPU kernel.

Online-softmax over KV blocks with fp32 running (max, sum, acc) carried in
VMEM scratch across the innermost (sequential) KV-block grid axis. Handles
GQA (q heads grouped over kv heads), causal masking, sliding windows, and
gemma-style score softcap. Block sizes are MXU/VPU aligned (multiples of
128 on the lane dim); VMEM footprint per step = bq·d + 2·bk·d + bq·bk fp32
≈ 1.3 MB at (bq=128, bk=128, d=128).

The memory-roofline win vs the naive path: scores (Sq × Skv) never
materialize in HBM — exactly the term the §Perf hillclimb targets for
prefill_32k cells.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import dot_precision

NEG_INF = -1e30

# block indices are int32 on the TPU; a bare ``0`` in an index map turns
# int64 when x64 is on, and Mosaic refuses to lower that
_ZERO = np.int32(0)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  kv_steps: int, bq: int, bk: int, scale: float,
                  causal: bool, window: int, softcap: float, acc_dtype):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # (bq, d)
    k = k_ref[0]                       # (bk, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=dot_precision(q.dtype),
        preferred_element_type=acc_dtype) * scale    # (bq, bk)
    if softcap and softcap > 0:
        s = jnp.tanh(s / softcap) * softcap

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window and window > 0:
        mask &= k_pos > (q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        precision=dot_precision(v_ref.dtype),
        preferred_element_type=acc_dtype)

    @pl.when(ki == kv_steps - 1)
    def _store():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "bq", "bk", "interpret"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         softcap: float = 0.0, bq: int = 128,
                         bk: int = 128, interpret: bool = False):
    """Single-kv-head layout: q (BH, Sq, D), k/v (BH, Skv, D)."""
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, skv, bq, bk)
    kv_steps = skv // bk
    scale = 1.0 / math.sqrt(d)
    # running max/sum/acc in at least fp32; f64 inputs keep precision
    acc_dtype = jnp.promote_types(q.dtype, jnp.float32)
    kern = functools.partial(
        _flash_kernel, kv_steps=kv_steps, bq=bq, bk=bk, scale=scale,
        causal=causal, window=window, softcap=softcap,
        acc_dtype=acc_dtype)
    return pl.pallas_call(
        kern,
        grid=(bh, sq // bq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, _ZERO)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, _ZERO)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, _ZERO)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, _ZERO)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), acc_dtype),
            pltpu.VMEM((bq, 1), acc_dtype),
            pltpu.VMEM((bq, d), acc_dtype),
        ],
        interpret=interpret,
    )(q, k, v)
