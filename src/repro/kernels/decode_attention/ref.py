"""Pure-jnp oracle for the decode-attention kernel (plain softmax)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def decode_attention_ref(q, k, v, n, first, *, softcap: float = 0.0):
    """q: (B, H, D); k/v: (B, S, KVH, D); n, first: (B,). Slot b
    attends over positions ``[first[b], n[b])``; none gives zeros."""
    b, h, d = q.shape
    kvh = k.shape[2]
    s = jnp.einsum("bkgd,bskd->bkgs", q.reshape(b, kvh, h // kvh, d), k,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    pos = jnp.arange(k.shape[1])
    visible = (pos >= first[:, None]) & (pos < n[:, None])  # (B, S)
    s = jnp.where(visible[:, None, None, :], s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(visible[:, None, None, :], p, 0.0)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)
