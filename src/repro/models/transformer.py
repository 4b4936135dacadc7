"""Model assembly: init, forward (scan-over-periods + remat), prefill,
decode — for every family in the pool (dense / MoE / hybrid / ssm /
enc-dec / frontend-stub multimodal).

Each layer = mixer (attn | mamba | mlstm | slstm) + optional FFN
(dense MLP | MoE). Layer parameters are stacked over period instances and
scanned, so a 96-layer model lowers to one compact while-loop in HLO.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import moe as moe_mod
from . import ssm as ssm_mod
from .common import (ArchConfig, KeyGen, _init, apply_attn, apply_mlp,
                     cross_kv_from_encoder, init_attn, init_mlp,
                     lm_head_loss, rmsnorm)

TP_DEFAULT = 16  # production mesh model-axis size (vocab/expert padding)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ArchConfig, idx_in_period: int,
                with_cross: bool) -> Tuple[Dict, Dict]:
    kg = KeyGen(key)
    kind = cfg.layer_kind(idx_in_period)
    p: Dict = {}
    s: Dict = {}
    if kind == "attn":
        p["attn"], s["attn"] = init_attn(kg, cfg)
    elif kind == "mamba":
        p["mamba"], s["mamba"] = ssm_mod.init_mamba(kg, cfg)
    elif kind == "mlstm":
        p["mlstm"], s["mlstm"] = ssm_mod.init_mlstm(kg, cfg)
    elif kind == "slstm":
        p["slstm"], s["slstm"] = ssm_mod.init_slstm(kg, cfg)
    if with_cross:
        p["cross"], s["cross"] = init_attn(kg, cfg)
    if cfg.d_ff > 0 or cfg.layer_is_moe(idx_in_period):
        if cfg.layer_is_moe(idx_in_period):
            p["moe"], s["moe"] = moe_mod.init_moe(kg, cfg, TP_DEFAULT)
        else:
            p["mlp"], s["mlp"] = init_mlp(kg, cfg)
    return p, s


def _stack_specs(s: Dict) -> Dict:
    return jax.tree.map(lambda spec: ("layers",) + spec, s,
                        is_leaf=lambda x: isinstance(x, tuple)
                        and all(isinstance(e, str) for e in x))


def init_params(cfg: ArchConfig, key) -> Tuple[Dict, Dict]:
    """Returns (params, specs). specs mirrors params with logical-axis
    tuples on every leaf — the planner's input."""
    kg = KeyGen(key)
    vpad = cfg.padded_vocab(TP_DEFAULT)
    params: Dict = {
        # N(0, 1/d): unit-variance inputs after the sqrt(d) embed scaling
        # and modest logits when tied as the unembedding.
        "embed": _init(kg(), (vpad, cfg.d_model), cfg.dtype,
                       scale=1.0 / math.sqrt(cfg.d_model)),
        "final_ln": jnp.zeros((cfg.d_model,), cfg.dtype),
    }
    specs: Dict = {
        "embed": ("vocab", "embed"),
        "final_ln": ("embed",),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = _init(kg(), (vpad, cfg.d_model), cfg.dtype,
                                  scale=1.0 / math.sqrt(cfg.d_model))
        specs["unembed"] = ("vocab", "embed")

    def stack_layers(idx: int, with_cross: bool):
        keys = jax.random.split(kg(), cfg.n_periods)
        p0, s0 = _init_layer(keys[0], cfg, idx, with_cross)
        stacked = jax.vmap(
            lambda k: _init_layer(k, cfg, idx, with_cross)[0])(keys)
        return stacked, _stack_specs(s0)

    blocks, bspecs = [], []
    for i in range(cfg.period):
        p, s = stack_layers(i, with_cross=cfg.is_encdec)
        blocks.append(p)
        bspecs.append(s)
    params["blocks"] = blocks
    specs["blocks"] = bspecs

    if cfg.is_encdec:
        enc_cfg = cfg
        n_enc_periods = cfg.enc_layers
        keys = jax.random.split(kg(), n_enc_periods)
        p0, s0 = _init_layer(keys[0], cfg, 0, with_cross=False)
        params["encoder"] = jax.vmap(
            lambda k: _init_layer(k, cfg, 0, False)[0])(keys)
        specs["encoder"] = _stack_specs(s0)
        params["enc_final_ln"] = jnp.zeros((cfg.d_model,), cfg.dtype)
        specs["enc_final_ln"] = ("embed",)
    return params, specs


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _apply_layer(p, x, cfg: ArchConfig, idx_in_period: int, *, positions,
                 cache=None, enc_out=None, valid=None):
    """One layer. Returns (x, new cache, MoE stats or None)."""
    kind = cfg.layer_kind(idx_in_period)
    new_cache = None
    stats = None
    if kind == "attn":
        window = cfg.layer_window(idx_in_period)
        x, new_cache = apply_attn(p["attn"], x, cfg, positions=positions,
                                  window=window,
                                  cache=None if cache is None
                                  else cache.get("kv"), valid=valid)
        new_cache = None if new_cache is None else {"kv": new_cache}
    elif kind == "mamba":
        x, st = ssm_mod.apply_mamba(p["mamba"], x, cfg,
                                    None if cache is None
                                    else cache.get("ssm"), valid=valid)
        new_cache = None if st is None else {"ssm": st}
    elif kind == "mlstm":
        x, st = ssm_mod.apply_mlstm(p["mlstm"], x, cfg,
                                    None if cache is None
                                    else cache.get("ssm"))
        new_cache = None if st is None else {"ssm": st}
    elif kind == "slstm":
        x, st = ssm_mod.apply_slstm(p["slstm"], x, cfg,
                                    None if cache is None
                                    else cache.get("ssm"))
        new_cache = None if st is None else {"ssm": st}
    if "cross" in p:
        if enc_out is not None:
            ckv = cross_kv_from_encoder(p["cross"], enc_out, cfg)
        elif cache is not None and "cross_kv" in cache:
            ckv = cache["cross_kv"]
        else:
            ckv = None
        if ckv is not None:
            x, _ = apply_attn(p["cross"], x, cfg, positions=positions,
                              cross_kv=ckv)
            if new_cache is not None:
                new_cache["cross_kv"] = ckv
    if "moe" in p:
        x, stats = moe_mod.apply_moe(p["moe"], x, cfg, valid=valid)
    elif "mlp" in p:
        x = apply_mlp(p["mlp"], x, cfg)
    return x, new_cache, stats


def _sum_stats(per_layer: List[Dict]) -> Dict:
    """MoE stats of a period's layers: per-expert counts stacked
    (layers, experts), the scalars summed."""
    if not per_layer:
        return {}
    out = {"expert_counts": jnp.stack([st["expert_counts"]
                                       for st in per_layer])}
    for key in ("assign_held", "assign_total", "dropped"):
        out[key] = sum(st[key] for st in per_layer)
    return out


def forward(params, x, cfg: ArchConfig, *, positions, caches=None,
            enc_out=None, valid=None, with_stats: bool = False):
    """x: (B, S, D) embeddings. caches: list per idx_in_period of stacked
    cache pytrees (leading dim n_periods) or None. valid: (B,) count of
    real tokens at the front of each row (None = all). Returns (x,
    caches), or (x, caches, stats) ``with_stats``: the MoE layers'
    ``expert_counts`` (moe layers, held experts) and summed
    ``assign_held``, ``assign_total``, ``dropped``."""
    blocks = params["blocks"]

    def seq_constraint(x):
        """Activation anchoring between layers: batch dim pinned to the
        planner's choice (GSPMD propagation can drift to replication
        inside scanned+remat'd bodies — a silent 16× compute waste), and
        optionally seq→model (Megatron-SP analogue) so remat checkpoints
        shard over the TP degree."""
        want_seq = cfg.seq_shard and x.shape[1] % 16 == 0
        if not want_seq and not cfg.act_batch_axes:
            return x
        try:
            from jax.sharding import PartitionSpec as P_

            b_spec = (tuple(cfg.act_batch_axes) if cfg.act_batch_axes
                      else P_.UNCONSTRAINED)
            s_spec = "model" if want_seq else P_.UNCONSTRAINED
            if want_seq and cfg.act_batch_axes \
                    and "model" in cfg.act_batch_axes:
                s_spec = P_.UNCONSTRAINED
            return jax.lax.with_sharding_constraint(
                x, P_(b_spec, s_spec, P_.UNCONSTRAINED))
        except Exception:
            return x  # no mesh / axis missing: constraint is a no-op

    def body(carry, xs):
        # the caches ride in the carry: each period reads its slice and
        # writes it back, so a step updates the stacked buffers in place
        # (as scan xs → ys they would be rebuilt whole every call); what
        # a layer adds to its cache (cross-KV at prefill) goes out as ys
        x, cs = carry
        x = seq_constraint(x)
        bp, j = xs
        cc = ([jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                   a, j, keepdims=False), c) for c in cs]
              if cs is not None else [None] * cfg.period)
        new_cc, per_layer = [], []
        for i in range(cfg.period):
            x, nc, st = _apply_layer(bp[i], x, cfg, i, positions=positions,
                                     cache=cc[i], enc_out=enc_out,
                                     valid=valid)
            new_cc.append(nc)
            if st is not None:
                per_layer.append(st)
        x = seq_constraint(x)
        ys = None
        if cs is not None:
            cs = [{key: jax.tree.map(
                      lambda a, n: jax.lax.dynamic_update_index_in_dim(
                          a, n, j, 0), c[key], nc[key])
                   for key in c} for c, nc in zip(cs, new_cc)]
            ys = [{key: val for key, val in nc.items() if key not in c}
                  for c, nc in zip(cc, new_cc)]
        if with_stats:
            return (x, cs), (ys, _sum_stats(per_layer))
        return (x, cs), ys

    if cfg.remat == "full":
        body = jax.checkpoint(body)
    elif cfg.remat == "dots":
        # save matmul outputs: backward skips recompute (≈1 fewer
        # all-gather wave of FSDP params) at ~2-3× checkpoint memory
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    (x, caches), ys = jax.lax.scan(
        body, (x, caches), (blocks, jnp.arange(cfg.n_periods)))
    added, stats = ys if with_stats else (ys, None)
    if caches is not None:
        caches = [{**c, **a} for c, a in zip(caches, added)]
    if not with_stats:
        return x, caches
    if stats:
        counts = stats["expert_counts"]
        stats = {"expert_counts": counts.reshape((-1,) + counts.shape[2:]),
                 **{k: v.sum() for k, v in stats.items()
                    if k != "expert_counts"}}
    return x, caches, stats


def encode(params, src_embeds, cfg: ArchConfig):
    """Encoder stack (non-causal attention) for enc-dec archs."""
    enc = params["encoder"]
    positions = jnp.arange(src_embeds.shape[1])

    def body(x, bp):
        h = rmsnorm(x, bp["attn"]["ln"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, bp["attn"]["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, bp["attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, bp["attn"]["wv"])
        if cfg.qkv_bias:
            q, k, v = q + bp["attn"]["bq"], k + bp["attn"]["bk"], \
                v + bp["attn"]["bv"]
        from .common import plain_attention, rope
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        out = plain_attention(q, k, v, causal=False)
        x = x + jnp.einsum("bshk,hkd->bsd", out,
                           bp["attn"]["wo"]).astype(x.dtype)
        x = apply_mlp(bp["mlp"], x, cfg)
        return x, None

    if cfg.remat == "full":
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, src_embeds, enc)
    return rmsnorm(x, params["enc_final_ln"], cfg.norm_eps)


def embed_tokens(params, tokens, cfg: ArchConfig):
    x = params["embed"][tokens]
    if cfg.scale_embed:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)
    return x.astype(cfg.residual_dtype or cfg.dtype)


# ---------------------------------------------------------------------------
# Top-level steps (loss / prefill / decode)
# ---------------------------------------------------------------------------

def loss_fn(params, batch, cfg: ArchConfig):
    """batch: {'tokens' or 'embeds', 'labels', optional 'src_embeds'}."""
    if cfg.embeds_input and "embeds" in batch:
        x = batch["embeds"].astype(cfg.dtype)
    else:
        x = embed_tokens(params, batch["tokens"], cfg)
    enc_out = None
    if cfg.is_encdec:
        enc_out = encode(params, batch["src_embeds"].astype(cfg.dtype),
                         cfg)
    positions = jnp.arange(x.shape[1])
    x, _ = forward(params, x, cfg, positions=positions, enc_out=enc_out)
    return lm_head_loss(params, x, batch["labels"], cfg,
                        cfg.padded_vocab(TP_DEFAULT))


def init_caches(cfg: ArchConfig, batch: int, max_seq: int,
                cross_len: int = 0, uniform_index: bool = False):
    """Stacked decode caches: list per idx_in_period. ``cross_len`` > 0
    adds encoder cross-KV slots (enc-dec decode entry point).
    ``uniform_index`` → scalar per-layer position (steady-state decode;
    cheap DUS updates) instead of per-slot positions (continuous
    batching)."""
    caches = []
    for i in range(cfg.period):
        kind = cfg.layer_kind(i)
        if kind == "attn":
            shape = (cfg.n_periods, batch, max_seq, cfg.kv_heads,
                     cfg.head_dim)
            idx_shape = (cfg.n_periods,) if uniform_index \
                else (cfg.n_periods, batch)
            c = {"kv": {"k": jnp.zeros(shape, cfg.dtype),
                        "v": jnp.zeros(shape, cfg.dtype),
                        "index": jnp.zeros(idx_shape, jnp.int32)}}
        else:
            if kind == "mamba":
                st = ssm_mod.init_mamba_state(cfg, batch)
            else:
                st = ssm_mod.init_xlstm_state(cfg, kind, batch)
            c = {"ssm": jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a[None], (cfg.n_periods,) + a.shape), st)}
        if cfg.is_encdec and cross_len > 0:
            c["cross_kv"] = (
                jnp.zeros((cfg.n_periods, batch, cross_len, cfg.kv_heads,
                           cfg.head_dim), cfg.dtype),
                jnp.zeros((cfg.n_periods, batch, cross_len, cfg.kv_heads,
                           cfg.head_dim), cfg.dtype))
        caches.append(c)
    return caches


def prefill(params, batch, cfg: ArchConfig, max_seq: int):
    """Run the prompt through the model, filling caches.
    Returns (caches, last_token_logits)."""
    if cfg.embeds_input and "embeds" in batch:
        x = batch["embeds"].astype(cfg.dtype)
    else:
        x = embed_tokens(params, batch["tokens"], cfg)
    b, s = x.shape[0], x.shape[1]
    enc_out = None
    if cfg.is_encdec:
        enc_out = encode(params, batch["src_embeds"].astype(cfg.dtype),
                         cfg)
    caches = init_caches(cfg, b, max_seq)
    positions = jnp.arange(s)
    x, caches = forward(params, x, cfg, positions=positions,
                        caches=caches, enc_out=enc_out)
    x = rmsnorm(x[:, -1:], params["final_ln"], cfg.norm_eps)
    w = params.get("unembed", params["embed"])
    from .common import mask_padded_vocab
    logits = mask_padded_vocab(jnp.einsum("btd,vd->btv", x, w),
                               cfg.vocab)[:, 0]
    return caches, logits


def _positions(caches, x):
    """Positions of this call's tokens, from any attention cache index
    (all layers share it); zeros where there is none."""
    pos0 = None
    for c in caches:
        if c is not None and "kv" in c:
            pos0 = c["kv"]["index"][0]
            break
    if pos0 is None:
        pos0 = jnp.zeros((x.shape[0],), jnp.int32)
    if pos0.ndim == 0:  # uniform decode position
        return pos0 + jnp.arange(x.shape[1])
    return pos0[:, None] + jnp.arange(x.shape[1])[None, :]


def _head(params, x, cfg: ArchConfig):
    """Final norm + unembedding: (B, T, D) → (B, T, Vpad) logits."""
    from .common import mask_padded_vocab, softcap

    x = rmsnorm(x, params["final_ln"], cfg.norm_eps)
    w = params.get("unembed", params["embed"])
    logits = jnp.einsum("btd,vd->btv", x, w)
    return mask_padded_vocab(softcap(logits, cfg.logit_softcap), cfg.vocab)


def prefill_chunk(params, tokens, caches, cfg: ArchConfig, valid):
    """One fixed-size chunk of a prefill, continuing each row from its
    caches (KV written at the cache index; conv and recurrent state
    carried in and out). tokens: (B, C) int32, of which the first
    ``valid[b]`` are real and the rest padding. Returns (caches, logits
    at each row's last real token (B, Vpad), MoE stats)."""
    x = embed_tokens(params, tokens, cfg)
    x, caches, stats = forward(params, x, cfg,
                               positions=_positions(caches, x),
                               caches=caches, valid=valid, with_stats=True)
    last = jnp.take_along_axis(
        x, (valid.astype(jnp.int32) - 1)[:, None, None], axis=1)
    return caches, _head(params, last, cfg)[:, 0], stats


def decode_step(params, tokens, caches, cfg: ArchConfig, *, enc_out=None,
                valid=None, with_stats: bool = False):
    """One decode step. tokens: (B, 1) int32; valid: (B,) 1 for rows
    that advance, 0 for rows whose caches stay as they were (None = all
    advance). Returns (logits, caches), or (logits, caches, MoE stats)
    ``with_stats``."""
    x = embed_tokens(params, tokens, cfg)
    out = forward(params, x, cfg, positions=_positions(caches, x),
                  caches=caches, enc_out=enc_out, valid=valid,
                  with_stats=with_stats)
    logits = _head(params, out[0], cfg)[:, 0]
    return (logits,) + tuple(out[1:])
