"""Public flash-attention wrapper: GQA layout handling + dispatch.

(B, S, H, D) GQA tensors are regrouped to (B·KVH·G, S, D) with K/V
broadcast over the G query-head groups, run through the Pallas kernel,
and regrouped back. Dispatch: Pallas on TPU (or forced for tests);
otherwise the jnp oracle.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from .. import interpret_mode
from .flash_attention import flash_attention_bhsd
from .ref import attention_ref


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, bq: int = 128, bk: int = 128,
                    force_pallas: bool = False,
                    interpret: Optional[bool] = None):
    if interpret_mode() and not force_pallas:
        return attention_ref(q, k, v, causal=causal, window=window,
                             softcap=softcap)
    if interpret is None:   # tests pass it; else the process decides
        interpret = interpret_mode()
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    # (B, S, H, D) → (B·H, S, D) with kv broadcast across groups
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(
        b * h, skv, d)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(
        b * h, skv, d)
    out = flash_attention_bhsd(
        qf, kf, vf, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, interpret=interpret)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
