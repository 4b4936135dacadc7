"""Single-token decode attention over per-slot KV caches, as a Pallas
TPU kernel that reads each slot's K and V only up to its length.

One query per slot attends over that slot's visible positions
``[first[b], n[b])`` of the caches, which stay as they are: (B, S, KVH,
D), seen through the reshape (B, S·KVH, D), whose row ``t·KVH + h`` is
position t of KV head h. On the TPU that reshape is free: a cache
position's KVH rows fill whole (8, 128) tiles when KVH is a multiple of
8, so both shapes have one byte order (a (B, S, KVH·D) view would not,
and XLA would transpose the whole cache to make it).

The grid is (slot, KV block). The lengths and first positions are
prefetched as scalars, and the K and V index maps clamp the block index
into the slot's visible blocks: a step outside them repeats the block
index of the step before, which the pipeline does not fetch again, and
its compute is skipped. A step scores all H query heads against all of
the block's rows in one matmul, (H, D) by (block·KVH, D), and masks the
pairs whose KV head is not the query head's; the masked probabilities
are exact zeros, so a second matmul with the V rows gives each head's
sum. The KVH-fold extra multiply-adds go to the matrix unit, which the
block's bytes leave idle. Online softmax across a slot's blocks keeps
f32 running (max, sum, acc) per query head in VMEM scratch.
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

NEG_INF = np.float32(-1e30)

# block indices are int32 on the TPU; a bare ``0`` in an index map turns
# int64 when x64 is on, and Mosaic refuses to lower that
_ZERO = np.int32(0)


def kv_block_index(j, first, n, block: int):
    """KV block that grid step ``j`` of a slot fetches: ``j`` clamped to
    the blocks that hold the slot's visible positions ``[first, n)``. A
    step past the last of them maps to the last, one before the first
    to the first, and an empty slot (``n == 0``) to its first block."""
    j, first, n, block = (jnp.asarray(x, jnp.int32)
                          for x in (j, first, n, block))
    lo = jax.lax.div(first, block)
    hi = jnp.maximum(jax.lax.div(n + block - 1, block) - 1, lo)
    return jnp.minimum(jnp.maximum(j, lo), hi)


def _decode_kernel(n_ref, first_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                   l_ref, acc_ref, *, block: int, kv_heads: int,
                   groups: int, seq_len: int, scale: float,
                   softcap: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    n = n_ref[b]
    first = first_ref[b]
    start = j * block
    rows = block * kv_heads

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the block holds a visible position (so no head's scores are all
    # masked, and its running max is a real score after the block).
    # Constants are np.int32 and np.float32: under x64 a Python number
    # would turn the arithmetic 64-bit, which Mosaic does not lower
    @pl.when((start < n) & (start + block > first))
    def _step():
        q = q_ref[0]                                         # (H, D)
        s = jax.lax.dot_general(
            q, k_ref[0], (((1,), (1,)), ((), ())),
            precision=dot_precision(q.dtype),
            preferred_element_type=jnp.float32) * scale      # (H, rows)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32,
                                 s.shape)
        head = jax.lax.div(iota(0), np.int32(groups))
        row = iota(1)
        pos = start + jax.lax.div(row, np.int32(kv_heads))
        keep = ((jax.lax.rem(row, np.int32(kv_heads)) == head)
                & (pos >= first) & (pos < n))
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[...]                                  # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0]                                         # (rows, D)
        if seq_len % block:
            # rows past the cache's end (a last block that S does not
            # fill) hold whatever the DMA left: zero them, so that
            # 0 · garbage cannot turn the sum into NaN
            in_cache = jax.lax.broadcasted_iota(
                jnp.int32, (rows, 1), 0) < (seq_len - start) * kv_heads
            v = jnp.where(in_cache, v, jnp.zeros_like(v))
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=dot_precision(v.dtype),
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _store():
        o_ref[0] = (acc_ref[...] / jnp.maximum(
            l_ref[...], np.float32(1e-30))).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "block",
                                             "interpret"))
def decode_attention_kernel(q, k, v, n, first, *, softcap: float = 0.0,
                            block: int = 512, interpret: bool = False):
    """q: (B, H, D), query head ``kvh·G + g`` attending with KV head
    kvh; k/v: (B, S, KVH, D); n, first: (B,) int32, slot b's visible
    positions ``[first[b], n[b])`` (``n[b] == 0``: none, zeros out).
    ``block`` positions a grid step (block·KVH a multiple of 8, 16 for
    bf16, or ``block == S``); S need not be a multiple of it. Returns
    (B, H, D)."""
    bsz, heads, d = q.shape
    seq_len, kvh = k.shape[1], k.shape[2]
    block = min(block, seq_len)
    rows = block * kvh
    kern = functools.partial(
        _decode_kernel, block=block, kv_heads=kvh, groups=heads // kvh,
        seq_len=seq_len, scale=np.float32(1.0 / math.sqrt(d)),
        softcap=np.float32(softcap))

    def kv_map(b, j, n_ref, first_ref):
        return (b, kv_block_index(j, first_ref[b], n_ref[b], block), _ZERO)

    def q_map(b, j, n_ref, first_ref):
        return (b, _ZERO, _ZERO)

    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, pl.cdiv(seq_len, block)),
            in_specs=[
                pl.BlockSpec((1, heads, d), q_map),
                pl.BlockSpec((1, rows, d), kv_map),
                pl.BlockSpec((1, rows, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, heads, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(n.astype(jnp.int32), first.astype(jnp.int32), q,
      k.reshape(bsz, seq_len * kvh, d), v.reshape(bsz, seq_len * kvh, d))
