"""Common model substrate: config, layers, attention, losses.

Models are pure-function JAX: parameters are nested dicts of arrays, every
leaf carries *logical axis names* (in a parallel `specs` tree) consumed by
the auto-sharding planner — the LM-scale incarnation of the paper's
``pfor(output=…, input=…, transfer=…)`` dataflow clauses.

Layer stacks are built as scan-over-periods: the repeating block pattern
(1 for homogeneous transformers, 2 for gemma2 local/global and xlstm
mLSTM/sLSTM, 8 for jamba attn/mamba interleave) is unrolled inside the scan
body while the scan runs over period instances — keeping HLO compact enough
to compile 96-layer models on the CPU dry-run host.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

@dataclass
class ArchConfig:
    name: str
    family: str                  # 'dense' | 'moe' | 'encdec' | 'hybrid' | 'ssm' | 'vlm' | 'audio'
    layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    kv_heads: int = 12
    d_ff: int = 3072
    vocab: int = 50304
    head_dim: Optional[int] = None
    # MoE
    n_experts: int = 0
    experts_topk: int = 0
    n_shared_experts: int = 0
    expert_d_ff: int = 0
    moe_every: int = 1           # MoE on layers where (idx % moe_every)==moe_offset
    moe_offset: int = 0
    capacity_factor: float = 1.25
    # attention flavor
    mlp_act: str = "silu"        # 'silu' | 'gelu' | 'sqrelu' | 'relu'
    qkv_bias: bool = False
    sliding_window: int = 0      # gemma2 local layers
    alt_local_global: bool = False
    attn_softcap: float = 0.0
    logit_softcap: float = 0.0
    rope_theta: float = 10000.0
    use_rope: bool = True        # False: attention with no positional
                                 # encoding (jamba)
    scale_embed: bool = True     # multiply embeddings by sqrt(d_model)
    # hybrid (jamba): period pattern of layer kinds
    period: int = 1
    attn_every: int = 1          # attention at idx % period == attn_idx
    attn_idx: int = 0
    ssm_state: int = 16          # mamba state size
    ssm_expand: int = 2
    ssm_conv: int = 4            # mamba causal conv1d width
    ssm_dt_rank: int = 0         # rank of the delta projection
                                 # (0 → ceil(d_model / 16), Mamba's rule)
    experts_held: Optional[Tuple[int, int]] = None
    # ^ [lo, hi) of the routed experts this chip holds (expert
    #   parallelism); None = all of them. The router keeps all
    #   n_experts outputs; the layer computes only its own experts' part.
    # xlstm
    xlstm_pattern: Tuple[str, ...] = ()
    # encoder-decoder
    enc_layers: int = 0
    is_encdec: bool = False
    # frontend stub (audio frames / vision patches): inputs are embeddings
    embeds_input: bool = False
    # numerics / training
    dtype: Any = jnp.bfloat16
    residual_dtype: Any = None   # the residual stream's dtype (None:
                                 # dtype); each layer's input is normed
                                 # back to dtype
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    # parallelism knobs (filled by configs; tuned by hillclimb)
    microbatch: int = 1          # grad-accumulation steps
    remat: str = "full"          # 'full' | 'none'
    attn_chunk: int = 1024       # online-softmax KV chunk for long seq
    fused_xent: bool = True      # vocab-sharded cross-entropy
    opt_8bit: bool = False       # int8 Adam moments
    seq_shard: bool = False      # sequence-parallel residual stream: remat
                                 # checkpoints shard seq over `model`
    act_batch_axes: Optional[Tuple[str, ...]] = None
    # ^ planner-chosen mesh axes for the activation batch dim; anchored
    #   between layers so GSPMD's propagation never drifts to replication
    #   inside the scanned/remat'd body (runtime knob, set by launch)
    moe_expert_axes: Optional[Tuple[str, ...]] = None
    # ^ planner-chosen mesh axes for the expert dim of MoE dispatch
    #   buffers (same anchoring rationale, applied inside apply_moe)
    moe_capacity_axes: Optional[Tuple[str, ...]] = None
    # ^ mesh axes for the capacity dim (covers axes experts cannot)
    force_strategy: Optional[str] = None   # hillclimb: pin the planner
    use_pallas: bool = False     # TPU kernels (validated separately)
    # skip list: shapes this arch cannot run (with reason)
    skip_shapes: Dict[str, str] = field(default_factory=dict)

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.d_model // self.n_heads
        if self.period == 1 and self.alt_local_global:
            self.period = 2
        if self.xlstm_pattern and self.period == 1:
            self.period = len(self.xlstm_pattern)

    @property
    def n_periods(self) -> int:
        assert self.layers % self.period == 0, (self.name, self.layers,
                                                self.period)
        return self.layers // self.period

    def layer_kind(self, idx_in_period: int) -> str:
        if self.xlstm_pattern:
            return self.xlstm_pattern[idx_in_period]
        if self.family == "hybrid":
            return "attn" if idx_in_period == self.attn_idx else "mamba"
        return "attn"

    def layer_is_moe(self, idx_in_period: int) -> bool:
        if self.n_experts == 0:
            return False
        return idx_in_period % self.moe_every == self.moe_offset

    def layer_window(self, idx_in_period: int) -> int:
        if self.alt_local_global:
            return self.sliding_window if idx_in_period % 2 == 0 else 0
        return self.sliding_window  # 0 = no window; mistral: all layers

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or -(-self.d_model // 16)

    @property
    def held_range(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    def padded_vocab(self, tp: int = 16, align: int = 128) -> int:
        q = tp * align
        return ((self.vocab + q - 1) // q) * q

    def param_count(self) -> int:
        """Approximate total parameters (for roofline MODEL_FLOPS)."""
        d, v = self.d_model, self.vocab
        total = v * d  # embedding
        if not self.tie_embeddings:
            total += v * d
        for p in range(self.period):
            kind = self.layer_kind(p)
            if kind == "attn":
                total_l = d * self.n_heads * self.head_dim \
                    + 2 * d * self.kv_heads * self.head_dim \
                    + self.n_heads * self.head_dim * d
            elif kind == "mamba":
                inner = self.ssm_expand * d
                n, r = self.ssm_state, self.dt_rank
                total_l = d * inner * 2 + inner * d \
                    + inner * (r + 2 * n) + r * inner \
                    + inner * (self.ssm_conv + n + 3)
            elif kind in ("mlstm", "slstm"):
                inner = d * 2
                total_l = 4 * d * inner + inner * d
            else:
                total_l = 0
            if self.layer_is_moe(p):
                eff = self.expert_d_ff or self.d_ff
                lo, hi = self.held_range
                total_l += (hi - lo) * 3 * d * eff
                total_l += self.n_shared_experts * 3 * d * eff
                total_l += d * self.n_experts  # router
            elif self.d_ff > 0 and kind in ("attn", "mamba"):
                mult = 3 if self.mlp_act in ("silu", "gelu") else 2
                total_l += mult * d * self.d_ff
            total += total_l * self.n_periods
        if self.is_encdec:
            total = int(total * 1.6)  # encoder stack + cross attention
        return int(total)

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed top-k experts)."""
        if self.n_experts == 0:
            return self.param_count()
        d = self.d_model
        eff = self.expert_d_ff or self.d_ff
        lo, hi = self.held_range
        per_layer_all = (hi - lo) * 3 * d * eff
        per_layer_act = (min(self.experts_topk, hi - lo)
                         + self.n_shared_experts) \
            * 3 * d * eff
        n_moe_layers = sum(1 for p in range(self.period)
                           if self.layer_is_moe(p)) * self.n_periods
        return self.param_count() - n_moe_layers * (per_layer_all -
                                                    per_layer_act)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def _init(key, shape, dtype, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(shape[0] if shape else 1)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


class KeyGen:
    def __init__(self, key):
        self.key = key

    def __call__(self):
        self.key, sub = jax.random.split(self.key)
        return sub


# ---------------------------------------------------------------------------
# Primitive layers
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps=1e-6):
    """RMSNorm in float32; the output takes the scale's dtype (the
    model's), so a float32 residual stream feeds its matmuls in it."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(scale.dtype)


def rope(x, positions, theta=10000.0):
    """x: (..., S, H, D). positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (...,S,half)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(x, cap):
    if cap and cap > 0:
        return jnp.tanh(x / cap) * cap
    return x


def _act(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "relu": jax.nn.relu,
        "sqrelu": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


# ---------------------------------------------------------------------------
# Attention (GQA, sliding window, softcap, chunked online softmax, KV cache)
# ---------------------------------------------------------------------------

def attention_scores_block(q, k, v, mask, cap):
    """Plain attention over one KV block. q:(B,Sq,H,D) k/v:(B,Skv,KVH,D)."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    qg = q.reshape(b, sq, kvh, groups, d)
    scores = jnp.einsum("bqkgd,bskd->bqkgs", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(d)
    scores = softcap(scores, cap)
    if mask is not None:
        scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
    return scores, groups


def _query_positions(q_offset, sq):
    """q_offset: scalar or (B,). Returns q_pos of shape (sq,) or (B,sq)."""
    off = jnp.asarray(q_offset)
    if off.ndim == 0:
        return off + jnp.arange(sq)
    return off[:, None] + jnp.arange(sq)[None, :]


def plain_attention(q, k, v, *, causal: bool, window: int = 0,
                    cap: float = 0.0, q_offset=0):
    b, sq, h, d = q.shape
    skv = k.shape[1]
    kvh = k.shape[2]
    q_pos = _query_positions(q_offset, sq)       # (sq,) or (B,sq)
    k_pos = jnp.arange(skv)
    mask = jnp.ones(q_pos.shape + (skv,), bool)
    if causal:
        mask &= k_pos <= q_pos[..., None]
    if window and window > 0:
        mask &= k_pos > (q_pos[..., None] - window)
    mask = jnp.broadcast_to(mask if mask.ndim == 3 else mask[None],
                            (b, sq, skv))
    scores, groups = attention_scores_block(q, k, v, mask, cap)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bqkgs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, d)


def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      cap: float = 0.0, chunk: int = 1024, q_offset=0):
    """Online-softmax attention, O(Sq·chunk) memory — the jnp twin of the
    Pallas flash kernel (kernels/flash_attention). KV chunks are read with
    dynamic_slice inside the scan (no padded/transposed copy of the whole
    cache is ever materialized)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    kvh = k.shape[2]
    groups = h // kvh
    # largest chunk ≤ requested that divides skv (avoids padding copies)
    c = min(chunk, skv)
    while skv % c:
        c -= 1
    chunk = c
    n_chunks = skv // chunk
    qg = q.reshape(b, sq, kvh, groups, d)
    q_pos = _query_positions(q_offset, sq)       # (sq,) or (B,sq)

    def body(carry, ci):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k, ci * chunk, chunk, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, ci * chunk, chunk, axis=1)
        k_pos = ci * chunk + jnp.arange(chunk)
        # f32 accumulation on bf16 inputs: native MXU behaviour on TPU;
        # keeps the CPU-legalization convert on the chunk (inside the
        # loop) instead of a hoisted full-cache f32 copy
        s = jnp.einsum("bqkgd,bskd->bqkgs", qg, kb,
                       preferred_element_type=jnp.float32)
        s = softcap(s / math.sqrt(d), cap)
        msk = jnp.broadcast_to(k_pos < skv, q_pos.shape + (chunk,))
        if causal:
            msk = msk & (k_pos <= q_pos[..., None])
        if window and window > 0:
            msk = msk & (k_pos > (q_pos[..., None] - window))
        msk = jnp.broadcast_to(msk if msk.ndim == 3 else msk[None],
                               (b, sq, chunk))
        s = jnp.where(msk[:, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] \
            + jnp.einsum("bqkgs,bskd->bqkgd", p.astype(q.dtype), vb,
                         preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, sq, kvh, groups), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, sq, kvh, groups), jnp.float32)
    a0 = jnp.zeros((b, sq, kvh, groups, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def attention(q, k, v, cfg: ArchConfig, *, causal=True, window=0,
              q_offset=0):
    skv = k.shape[1]
    if cfg.use_pallas:
        from repro.kernels.flash_attention import ops as flash_ops

        return flash_ops.flash_attention(
            q, k, v, causal=causal, window=window,
            softcap=cfg.attn_softcap)
    if skv > 2 * cfg.attn_chunk:
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 cap=cfg.attn_softcap,
                                 chunk=cfg.attn_chunk, q_offset=q_offset)
    return plain_attention(q, k, v, causal=causal, window=window,
                           cap=cfg.attn_softcap, q_offset=q_offset)


# ---------------------------------------------------------------------------
# Attention block (params + apply, with optional KV cache)
# ---------------------------------------------------------------------------

def init_attn(kg: KeyGen, cfg: ArchConfig) -> Tuple[Dict, Dict]:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    p = {
        "wq": _init(kg(), (d, h, hd), cfg.dtype),
        "wk": _init(kg(), (d, kvh, hd), cfg.dtype),
        "wv": _init(kg(), (d, kvh, hd), cfg.dtype),
        "wo": _init(kg(), (h, hd, d), cfg.dtype),
        "ln": jnp.zeros((d,), cfg.dtype),
    }
    s = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
        "ln": ("embed",),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), cfg.dtype)
        p["bk"] = jnp.zeros((kvh, hd), cfg.dtype)
        p["bv"] = jnp.zeros((kvh, hd), cfg.dtype)
        s["bq"] = ("heads", "head_dim")
        s["bk"] = ("kv_heads", "head_dim")
        s["bv"] = ("kv_heads", "head_dim")
    return p, s


def write_rows(cache, new, idx):
    """cache: (B, S, ...); new: (B, 1, ...); idx: (B,). Row b's one
    position goes to ``cache[b, idx[b]]``; an index at or past S writes
    nothing. A scatter of B positions, in place when the cache's buffer
    is donated (a select over all S positions would rewrite the whole
    cache to put in B of them)."""
    rows = jnp.arange(cache.shape[0])
    return cache.at[rows, idx].set(new[:, 0].astype(cache.dtype),
                                   mode="drop")


def apply_attn(p, x, cfg: ArchConfig, *, positions, window=0, cache=None,
               cross_kv=None, valid=None):
    """x: (B, S, D). cache: dict(k, v, index) for decode. cross_kv: (k, v)
    for encoder-decoder cross attention (ignores cache/causal). valid:
    (B,) count of real tokens at the front of each row (the rest pad a
    fixed-size prefill chunk); the cache index advances by it."""
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    pos_enc = (lambda t: rope(t, positions, cfg.rope_theta)) \
        if cfg.use_rope else (lambda t: t)
    if cross_kv is not None:
        k, v = cross_kv
        q = pos_enc(q)
        out = plain_attention(q, k, v, causal=False, window=0,
                              cap=cfg.attn_softcap)
    else:
        k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
        if cfg.qkv_bias:
            k = k + p["bk"]
            v = v + p["bv"]
        q = pos_enc(q)
        k = pos_enc(k)
        if cache is not None:
            # index: scalar () = uniform decode position (steady-state
            # serving; tiny dynamic-update-slice writes), or (B,) =
            # per-slot positions (continuous batching; one position per
            # row, scattered into the cache in place; under GSPMD the
            # scatter partitions along the batch axis, though no caller
            # shards per-slot caches: the sharded dry run decodes with
            # a scalar index)
            idx = cache["index"]
            s_new = k.shape[1]
            if idx.ndim == 0:
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
            elif s_new == 1:
                ck = write_rows(cache["k"], k, idx)
                cv = write_rows(cache["v"], v, idx)
            else:
                # a multi-token write (prefill, or one chunk of it):
                # the rows start together, at the first row's index
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), idx[0],
                    axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), idx[0],
                    axis=1)
            step = s_new if valid is None else valid.astype(idx.dtype)
            cache = {"k": ck, "v": cv, "index": idx + step}
            from repro.kernels.decode_attention import ops as decode_ops

            if idx.ndim == 1 and s_new == 1 and decode_ops.engaged(ck):
                # one query a slot: read each slot's K and V only up to
                # its length, in place (a row with valid 0 gets zeros)
                out = decode_ops.decode_attention(
                    q, ck, cv, idx, valid, window=window,
                    softcap=cfg.attn_softcap)
            else:
                out = attention(q, ck, cv, cfg, causal=True,
                                window=window, q_offset=idx)
        else:
            out = attention(q, k, v, cfg, causal=True, window=window)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return x + y.astype(x.dtype), cache


def cross_kv_from_encoder(p, enc_out, cfg: ArchConfig):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    return k, v


# ---------------------------------------------------------------------------
# MLP block
# ---------------------------------------------------------------------------

def init_mlp(kg: KeyGen, cfg: ArchConfig, d_ff: Optional[int] = None
             ) -> Tuple[Dict, Dict]:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    gated = cfg.mlp_act in ("silu", "gelu")
    p = {
        "wi": _init(kg(), (d, f), cfg.dtype),
        "wo": _init(kg(), (f, d), cfg.dtype),
        "ln": jnp.zeros((d,), cfg.dtype),
    }
    s = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed"), "ln": ("embed",)}
    if gated:
        p["wg"] = _init(kg(), (d, f), cfg.dtype)
        s["wg"] = ("embed", "mlp")
    return p, s


def apply_mlp(p, x, cfg: ArchConfig):
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    up = jnp.einsum("bsd,df->bsf", h, p["wi"])
    act = _act(cfg.mlp_act)
    if "wg" in p:
        gate = jnp.einsum("bsd,df->bsf", h, p["wg"])
        up = act(gate) * up
    else:
        up = act(up)
    y = jnp.einsum("bsf,fd->bsd", up, p["wo"])
    return x + y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def cross_entropy(logits, labels, vocab: int):
    """logits: (B,S,V) f32-able; labels: (B,S) int32; -100 → ignore.
    Entries ≥ vocab in the padded dimension are masked."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32),
        jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    valid = labels >= 0
    loss = jnp.where(valid, lse - gold, 0.0)
    return loss.sum() / jnp.maximum(valid.sum(), 1)


def mask_padded_vocab(logits, vocab: int):
    vpad = logits.shape[-1]
    if vpad == vocab:
        return logits
    mask = jnp.arange(vpad) < vocab
    return jnp.where(mask, logits, -1e30)


def lm_head_loss(params, x, labels, cfg: ArchConfig, padded_vocab: int):
    """Final norm + unembed + xent. With cfg.fused_xent the (B,S,V) logit
    tensor is consumed chunk-wise along S so only a chunk is ever live —
    the vocab axis itself is sharded by the planner."""
    x = rmsnorm(x, params["final_ln"], cfg.norm_eps)
    w = params.get("unembed", params["embed"])  # (Vpad, D)

    if cfg.logit_softcap:
        def logit_fn(chunk):
            return mask_padded_vocab(
                softcap(jnp.einsum("btd,vd->btv", chunk, w),
                        cfg.logit_softcap), cfg.vocab)
    else:
        def logit_fn(chunk):
            return mask_padded_vocab(
                jnp.einsum("btd,vd->btv", chunk, w), cfg.vocab)

    if not cfg.fused_xent:
        logits = logit_fn(x)
        return cross_entropy(logits, labels, cfg.vocab)

    # chunk along sequence to bound live logits
    b, s, d = x.shape
    n_chunks = min(8, s) if s >= 8 else 1
    while s % n_chunks:
        n_chunks -= 1
    xs = x.reshape(b, n_chunks, s // n_chunks, d)
    ls = labels.reshape(b, n_chunks, s // n_chunks)

    def body(carry, blk):
        tot, cnt = carry
        xc, lc = blk
        logits = logit_fn(xc)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(
            logits.astype(jnp.float32),
            jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        valid = lc >= 0
        tot = tot + jnp.where(valid, lse - gold, 0.0).sum()
        cnt = cnt + valid.sum().astype(jnp.float32)
        return (tot, cnt), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)),
        (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(ls, 1, 0)))
    return tot / jnp.maximum(cnt, 1.0)
