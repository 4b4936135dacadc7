"""Decode-attention wrapper: the per-slot single-token step's attention,
and what it reads.

The model's per-slot decode (``models/common.py`` ``apply_attn``) asks
:func:`engaged` whether to attend through the kernel, and the serving
engine counts what it read with :func:`kv_read_positions`; both go
through this module, so the block size and the engagement test live in
one place. Both look at a K cache, (..., S, KVH, D). The kernel engages
where it compiles (a TPU backend) and where its view of the cache is
free: D a multiple of 128 and KVH a multiple of 8, so a position's rows
fill whole (8, 128) tiles. Elsewhere the model keeps its jnp attention.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from .. import interpret_mode
from .decode_attention import decode_attention_kernel

# bytes of K a grid step (as many of V): 512 positions of 8 KV heads of
# 128 in bf16
BLOCK_BYTES = 1 << 20


def engaged(cache) -> bool:
    """Whether the per-slot decode over K cache ``cache`` attends
    through the kernel."""
    kv_heads, head_dim = cache.shape[-2:]
    return (not interpret_mode() and head_dim % 128 == 0
            and kv_heads % 8 == 0)


def block_size(cache) -> int:
    """KV positions a grid step over K cache ``cache``: ``BLOCK_BYTES``
    of it, a multiple of 16 positions, at most all S."""
    seq_len, kv_heads, head_dim = cache.shape[-3:]
    row = kv_heads * head_dim * jnp.dtype(cache.dtype).itemsize
    return min(seq_len, max(16, BLOCK_BYTES // row // 16 * 16))


def visible_range(idx, valid, max_seq: int, window: int):
    """Slot b's visible cache positions ``[first, n)`` for its query at
    position ``idx[b]``, whose own K and V are already written: causal,
    within ``window`` when it is positive, none where ``valid[b] == 0``
    (None: every row is valid)."""
    n = jnp.minimum(idx + 1, max_seq)
    if valid is not None:
        n = jnp.where(valid > 0, n, 0)
    first = jnp.maximum(idx + 1 - window, 0) if window > 0 \
        else jnp.zeros_like(n)
    return jnp.where(first < n, n, 0), jnp.minimum(first, n)


def decode_attention(q, k, v, idx, valid=None, *, window: int = 0,
                     softcap: float = 0.0):
    """q: (B, 1, H, D); k/v: (B, S, KVH, D) with this step's K and V
    written; idx: (B,) each slot's query position. Returns (B, 1, H,
    D): attention over each slot's visible positions, zeros for a slot
    with ``valid`` 0."""
    b, _, h, d = q.shape
    n, first = visible_range(idx, valid, k.shape[1], window)
    out = decode_attention_kernel(
        q.reshape(b, h, d), k, v, n, first, softcap=softcap,
        block=block_size(k), interpret=interpret_mode())
    return out.reshape(b, 1, h, d)


def kv_read_positions(lengths, cache, window: int = 0) -> int:
    """Positions of K cache ``cache`` (and as many of V) that one
    attention layer's per-slot decode reads in a step, for slots whose
    visible lengths are ``lengths`` (0 for an idle slot). Through the
    kernel: each slot's visible blocks (an idle slot still fetches
    one); otherwise: every position of every slot."""
    max_seq = cache.shape[-3]
    lengths = np.minimum(np.asarray(lengths, np.int64), max_seq)
    if not engaged(cache):
        return len(lengths) * max_seq
    block = block_size(cache)
    first = np.maximum(lengths - window, 0) if window > 0 \
        else np.zeros_like(lengths)
    lo = first // block
    hi = np.maximum(-(-lengths // block), lo + 1)
    return int(np.sum(np.minimum(hi * block, max_seq) - lo * block))
