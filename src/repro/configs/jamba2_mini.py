"""AI21-Jamba2-Mini [hybrid]: 32L d_model=4096 32H (GQA kv=8, head_dim
128), vocab 65536, untied embeddings, RMSNorm eps 1e-6
[hf: ai21labs/AI21-Jamba2-Mini config.json; arXiv:2403.19887].

Period-8 block: attention at index 4 (``attn_layer_offset`` 4,
``attn_layer_period`` 8) with no positional encoding, the published
Mamba-1 mixer everywhere else (``d_state`` 16, ``d_conv`` 4 with conv
bias, ``expand`` 2, ``dt_rank`` 256, no projection bias, RMSNorm on Δ, B
and C). FFN on odd indices (``expert_layer_offset`` 1,
``expert_layer_period`` 2): MoE, 16 experts, top-2, width 14336,
SiLU-gated, softmax over all router logits and no renormalisation; on
even indices a dense SiLU-gated MLP of width 14336 (the config's one
``intermediate_size`` read as both widths). Weights and matmul inputs in
bfloat16 (the published ``torch_dtype``); the residual stream is kept
in float32, so that the sixteen residual additions do not each round to
bfloat16.

:func:`ep_config` is one chip's share of an expert-parallel deployment:
the experts it holds and its slice of the vocabulary are both options,
as is the depth (whole periods; the rest would lie on further chips as
pipeline stages)."""

import dataclasses
from typing import Tuple

import jax.numpy as jnp

from repro.models.common import ArchConfig


def config() -> ArchConfig:
    return ArchConfig(
        name="jamba2_mini", family="hybrid",
        layers=32, d_model=4096, n_heads=32, kv_heads=8, head_dim=128,
        d_ff=14336, vocab=65536,
        period=8, attn_idx=4,
        n_experts=16, experts_topk=2, expert_d_ff=14336,
        moe_every=2, moe_offset=1,
        ssm_state=16, ssm_expand=2, ssm_conv=4, ssm_dt_rank=256,
        mlp_act="silu", tie_embeddings=False, norm_eps=1e-6,
        use_rope=False, scale_embed=False, residual_dtype=jnp.float32,
        remat="none",
    )


def ep_config(experts_held: Tuple[int, int] = (0, 8), vocab: int = 8192,
              layers: int = 8) -> ArchConfig:
    """One chip's share: ``experts_held`` = [lo, hi) of each MoE layer's
    16 experts (the router keeps all 16), the first ``vocab`` rows of
    the embedding and output head, and ``layers`` of the 32 (a multiple
    of the period). Every width is as published."""
    cfg = config()
    assert layers % cfg.period == 0, layers
    lo, hi = experts_held
    assert 0 <= lo < hi <= cfg.n_experts, experts_held
    return dataclasses.replace(
        cfg, name=f"jamba2_mini_e{lo}-{hi}_v{vocab}_l{layers}",
        layers=layers, vocab=vocab, experts_held=(lo, hi))


def smoke_config() -> ArchConfig:
    """Tiny widths, the published structure: one period of 8 layers,
    attention at 4, 8 experts top-2 on odd layers."""
    return ArchConfig(
        name="jamba2_mini_smoke", family="hybrid",
        layers=8, d_model=64, n_heads=4, kv_heads=2, d_ff=96,
        vocab=256, period=8, attn_idx=4,
        n_experts=8, experts_topk=2, expert_d_ff=96,
        moe_every=2, moe_offset=1,
        ssm_state=4, ssm_expand=2, ssm_conv=4, ssm_dt_rank=8,
        mlp_act="silu", tie_embeddings=False,
        use_rope=False, scale_embed=False,
        microbatch=1, remat="none", attn_chunk=16,
    )
