"""Plain reference of the Jamba block (Jamba2-Mini, Jamba-1.5), for the
tests: one sequence, float32 at ``"highest"`` matmul precision, no
kernels, no cache, no batching, no chunking.

It follows the published equations (``JambaMambaMixer``,
``JambaAttention``, ``JambaSparseMoeBlock`` of Hugging Face
transformers; arXiv:2403.19887) and reads the program's parameter tree
(``repro.models.transformer.init_params``), upcast to float32:

    layer i:  x += mixer_i(RMSNorm(x));  x += ffn_i(RMSNorm(x))
    mixer:    attention (GQA, causal, no positional encoding) at
              i % period == attn_idx, Mamba-1 elsewhere
    ffn:      MoE on MoE layers, a dense SiLU-gated MLP elsewhere
    logits  = unembed(RMSNorm(x))

Departures, each shared with the program: RMSNorm scales are stored as
``1 + scale`` (the repo's convention; zero-initialised = unit weight);
an MoE layer of a config with ``experts_held`` adds only its held
experts' part, as one chip of an expert-parallel deployment does.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f(t):
    return jnp.asarray(t, F32)


def rmsnorm(x, scale, eps):
    x = _f(x)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + _f(scale))


def mamba(p, x, cfg):
    """x: (S, D) → the mixer's output (S, D)."""
    s = x.shape[0]
    n, r, k = cfg.ssm_state, cfg.dt_rank, cfg.ssm_conv
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    xs, z = jnp.split(h @ _f(p["in_proj"]), 2, axis=-1)
    xc = jnp.concatenate([jnp.zeros((k - 1, xs.shape[1]), F32), xs])
    w = _f(p["conv_w"])
    conv = _f(p["conv_b"]) + sum(xc[j:j + s] * w[j] for j in range(k))
    u = jax.nn.silu(conv)
    dbc = u @ _f(p["x_proj"])
    dt_in, bm, cm = jnp.split(dbc, [r, r + n], axis=-1)
    dt_in = rmsnorm(dt_in, p["dt_ln"], cfg.norm_eps)
    bm = rmsnorm(bm, p["b_ln"], cfg.norm_eps)
    cm = rmsnorm(cm, p["c_ln"], cfg.norm_eps)
    dt = jax.nn.softplus(dt_in @ _f(p["dt_proj"]) + _f(p["dt_bias"]))
    a = -jnp.exp(_f(p["a_log"]))                       # (I, N)

    def step(state, inp):
        u_t, dt_t, b_t, c_t = inp
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return state, state @ c_t

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, F32), (u, dt, bm, cm))
    y = (y + _f(p["d_skip"]) * u) * jax.nn.silu(z)
    return y @ _f(p["out_proj"])


def attention(p, x, cfg):
    """Causal GQA with no positional encoding. x: (S, D)."""
    assert not cfg.use_rope, "the Jamba reference has no RoPE"
    s = x.shape[0]
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    q = jnp.einsum("sd,dhk->shk", h, _f(p["wq"]))
    k = jnp.einsum("sd,dhk->shk", h, _f(p["wk"]))
    v = jnp.einsum("sd,dhk->shk", h, _f(p["wv"]))
    groups = cfg.n_heads // cfg.kv_heads
    k = jnp.repeat(k, groups, axis=1)
    v = jnp.repeat(v, groups, axis=1)
    scores = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqs,shk->qhk", probs, v)
    return jnp.einsum("qhk,hkd->qd", out, _f(p["wo"]))


def _expert(h, wi, wg, wo):
    return (jax.nn.silu(h @ _f(wg)) * (h @ _f(wi))) @ _f(wo)


def mlp(p, x, cfg):
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    return _expert(h, p["wi"], p["wg"], p["wo"])


def moe(p, x, cfg):
    """Softmax over all router logits, top-k, no renormalisation; the
    held experts' weighted outputs summed."""
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    probs = jax.nn.softmax(h @ _f(p["router"])[:, :cfg.n_experts], -1)
    top_w, top_i = jax.lax.top_k(probs, cfg.experts_topk)
    lo, hi = cfg.held_range
    y = jnp.zeros_like(h)
    for e in range(lo, hi):
        weight = jnp.where(top_i == e, top_w, 0.0).sum(-1)   # (S,)
        y = y + weight[:, None] * _expert(
            h, p["wi"][e - lo], p["wg"][e - lo], p["wo"][e - lo])
    return y


def forward(params, tokens, cfg) -> np.ndarray:
    """Logits (S, vocab) of one sequence of token ids."""
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: _forward(p, t, cfg))(
            params, jnp.asarray(tokens, jnp.int32))
    return np.asarray(logits[:, :cfg.vocab])


def _forward(params, tokens, cfg):
    x = _f(params["embed"])[tokens]
    for per in range(cfg.n_periods):
        for i in range(cfg.period):
            lp = jax.tree.map(lambda t: t[per], params["blocks"][i])
            if cfg.layer_kind(i) == "attn":
                x = x + attention(lp["attn"], x, cfg)
            else:
                x = x + mamba(lp["mamba"], x, cfg)
            if "moe" in lp:
                x = x + moe(lp["moe"], x, cfg)
            else:
                x = x + mlp(lp["mlp"], x, cfg)
    x = rmsnorm(x, params["final_ln"], cfg.norm_eps)
    return x @ _f(params["unembed"]).T
