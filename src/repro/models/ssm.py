"""State-space / recurrent blocks: Mamba (jamba) and xLSTM (mLSTM/sLSTM).

The sequence recurrence is the one iteration dimension the planner must
never shard (knowledge-base entry 'ssm_scan': sequential on seq, parallel
on batch/feature). A whole sequence runs as a blocked associative scan
that carries the state between blocks; decode and chunked prefill keep an
explicit recurrent state — which is why these families run the long_500k
shape that quadratic attention cannot.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ArchConfig, KeyGen, _init, rmsnorm


# ---------------------------------------------------------------------------
# Mamba-1 mixer, as Jamba publishes it (JambaMambaMixer)
# ---------------------------------------------------------------------------
#
#   (x, z) = in_proj(h)                       no bias
#   x = silu(causal depthwise conv1d(x))      width ssm_conv, with bias
#   (Δ, B, C) = x_proj(x)                     ranks dt_rank, N, N
#   Δ, B, C = RMSNorm(Δ), RMSNorm(B), RMSNorm(C)   (Jamba's stabiliser)
#   Δ = softplus(dt_proj(Δ) + dt_bias);  A = -exp(A_log)
#   s_t = exp(Δ_t A) s_{t-1} + Δ_t x_t B_t ;  y_t = s_t C_t + D x_t
#   out = out_proj(y · silu(z))
#
# The scan runs in float32. Decode state per sequence: the last
# ssm_conv - 1 conv inputs and the (inner, N) recurrent state.

SCAN_CHUNK = 128   # prefill scan block: memory ∝ block, not prompt


def _uniform(key, shape, bound, dtype):
    return jax.random.uniform(key, shape, jnp.float32, -bound,
                              bound).astype(dtype)


def init_mamba(kg: KeyGen, cfg: ArchConfig) -> Tuple[Dict, Dict]:
    """Random weights drawn as Mamba's own init sets them: A_log =
    log(1..N) per channel, D = 1, dt_bias = softplus⁻¹(Δ₀) with Δ₀
    log-uniform in [1e-3, 1e-1], dt_proj uniform in ±dt_rank^-½, the
    conv as PyTorch's Conv1d default (uniform in ±1/√width)."""
    d = cfg.d_model
    inner = cfg.ssm_expand * d
    n, r, k = cfg.ssm_state, cfg.dt_rank, cfg.ssm_conv
    dt0 = jnp.exp(jax.random.uniform(
        kg(), (inner,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    dt0 = jnp.maximum(dt0, 1e-4)
    p = {
        "ln": jnp.zeros((d,), cfg.dtype),
        "in_proj": _init(kg(), (d, 2 * inner), cfg.dtype),
        "conv_w": _uniform(kg(), (k, inner), 1 / math.sqrt(k), cfg.dtype),
        "conv_b": _uniform(kg(), (inner,), 1 / math.sqrt(k), cfg.dtype),
        "x_proj": _init(kg(), (inner, r + 2 * n), cfg.dtype),
        "dt_ln": jnp.zeros((r,), cfg.dtype),
        "b_ln": jnp.zeros((n,), cfg.dtype),
        "c_ln": jnp.zeros((n,), cfg.dtype),
        "dt_proj": _uniform(kg(), (r, inner), r ** -0.5, cfg.dtype),
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
        "a_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, n + 1, dtype=jnp.float32), (inner, n))),
        "d_skip": jnp.ones((inner,), jnp.float32),
        "out_proj": _init(kg(), (inner, d), cfg.dtype),
    }
    s = {
        "ln": ("embed",),
        "in_proj": ("embed", "inner"),
        "conv_w": ("ssm", "inner"),
        "conv_b": ("inner",),
        "x_proj": ("inner", "ssm"),
        "dt_ln": ("ssm",),
        "b_ln": ("ssm",),
        "c_ln": ("ssm",),
        "dt_proj": ("ssm", "inner"),
        "dt_bias": ("inner",),
        "a_log": ("inner", "ssm"),
        "d_skip": ("inner",),
        "out_proj": ("inner", "embed"),
    }
    return p, s


def selective_scan(u, dt, Bm, Cm, a, h0, chunk: int = SCAN_CHUNK):
    """s_t = exp(Δ_t a) s_{t-1} + Δ_t u_t B_t ; y_t = s_t · C_t, from
    state ``h0``. u/dt: (B, L, I) f32; Bm/Cm: (B, L, N); a: (I, N), the
    negative decay; h0: (B, I, N). Returns (y (B, L, I), s_L).

    Blocks of ``chunk`` steps run as an associative scan and carry the
    state from block to block, so memory grows with the block and not
    with L. A step with Δ = 0 leaves the state as it was: padding."""
    b, l, inner = u.shape
    if l == 1:
        h = jnp.exp(dt[:, 0, :, None] * a) * h0 \
            + (dt[:, 0] * u[:, 0])[..., None] * Bm[:, 0, None, :]
        return (h * Cm[:, 0, None, :]).sum(-1)[:, None], h
    c = min(chunk, l)
    pad = (-l) % c
    if pad:
        widths = ((0, 0), (0, pad), (0, 0))
        u, dt, Bm, Cm = (jnp.pad(t, widths) for t in (u, dt, Bm, Cm))
    nb = (l + pad) // c

    def blocks(t):
        return jnp.moveaxis(t.reshape((b, nb, c) + t.shape[2:]), 1, 0)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    def body(h, blk):
        ub, dtb, bb, cb = blk
        a_bar = jnp.exp(dtb[..., None] * a)                  # (B,c,I,N)
        bx = (dtb * ub)[..., None] * bb[:, :, None, :]
        a_cum, h_loc = jax.lax.associative_scan(combine, (a_bar, bx),
                                                axis=1)
        hs = h_loc + a_cum * h[:, None]
        return hs[:, -1], (hs * cb[:, :, None, :]).sum(-1)

    h, ys = jax.lax.scan(body, h0, tuple(blocks(t)
                                         for t in (u, dt, Bm, Cm)))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nb * c, inner)[:, :l]
    return y, h


def _token_mask(valid, b: int, s: int):
    """(B, S) bool: which positions hold real tokens (None = all)."""
    if valid is None:
        return None
    return jnp.arange(s)[None, :] < valid[:, None]


def apply_mamba(p, x, cfg: ArchConfig, state: Optional[Dict] = None,
                valid=None):
    """x: (B, S, D). state: {'conv': (B, K-1, I), 'h': (B, I, N)} to
    continue from (decode, or the next chunk of a prefill), else None
    (a whole sequence from zero state: training). valid: (B,) count of
    real tokens at the front of each row; the padding after them leaves
    both states as they were. Returns (x + out, new state or None)."""
    b, s, d = x.shape
    inner = cfg.ssm_expand * d
    n, r, k = cfg.ssm_state, cfg.dt_rank, cfg.ssm_conv
    eps = cfg.norm_eps
    h_in = rmsnorm(x, p["ln"], eps)
    proj = jnp.einsum("bsd,di->bsi", h_in, p["in_proj"])
    xs, gate = jnp.split(proj, 2, axis=-1)

    # causal depthwise conv over [carried inputs | this call's inputs]
    prev = jnp.zeros((b, k - 1, inner), xs.dtype) if state is None \
        else state["conv"].astype(xs.dtype)
    xc = jnp.concatenate([prev, xs], axis=1)                  # (B,S+K-1,I)
    w = p["conv_w"].astype(jnp.float32)
    conv = p["conv_b"].astype(jnp.float32) + sum(
        xc[:, j:j + s].astype(jnp.float32) * w[j] for j in range(k))
    u = jax.nn.silu(conv).astype(cfg.dtype)                   # (B,S,I)

    dbc = jnp.einsum("bsi,ik->bsk", u, p["x_proj"])
    dt_in, Bm, Cm = jnp.split(dbc, [r, r + n], axis=-1)
    dt_in = rmsnorm(dt_in, p["dt_ln"], eps)
    Bm = rmsnorm(Bm, p["b_ln"], eps).astype(jnp.float32)
    Cm = rmsnorm(Cm, p["c_ln"], eps).astype(jnp.float32)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,ri->bsi", dt_in, p["dt_proj"]).astype(jnp.float32)
        + p["dt_bias"])
    mask = _token_mask(valid, b, s)
    if mask is not None:
        dt = jnp.where(mask[..., None], dt, 0.0)
    a = -jnp.exp(p["a_log"])
    u32 = u.astype(jnp.float32)

    if state is None and cfg.use_pallas:
        from repro.kernels.mamba_scan import ops as scan_ops

        y = scan_ops.mamba_scan(u32, dt, Bm, Cm, p["a_log"], p["d_skip"])
        new_state = None
    else:
        h0 = jnp.zeros((b, inner, n), jnp.float32) if state is None \
            else state["h"]
        y, h = selective_scan(u32, dt, Bm, Cm, a, h0)
        y = y + p["d_skip"] * u32
        new_state = None
        if state is not None:
            # the last K-1 conv inputs before each row's end
            end = jnp.full((b,), s, jnp.int32) if valid is None \
                else valid.astype(jnp.int32)
            conv_state = jax.vmap(
                lambda row, e: jax.lax.dynamic_slice_in_dim(
                    row, e, k - 1, axis=0))(xc, end)
            new_state = {"conv": conv_state.astype(state["conv"].dtype),
                         "h": h}

    y = (y * jax.nn.silu(gate.astype(jnp.float32))).astype(cfg.dtype)
    out = jnp.einsum("bsi,id->bsd", y, p["out_proj"])
    return x + out.astype(x.dtype), new_state


def init_mamba_state(cfg: ArchConfig, batch: int) -> Dict:
    inner = cfg.ssm_expand * cfg.d_model
    return {"conv": jnp.zeros((batch, cfg.ssm_conv - 1, inner), cfg.dtype),
            "h": jnp.zeros((batch, inner, cfg.ssm_state), jnp.float32)}


# ---------------------------------------------------------------------------
# xLSTM blocks
# ---------------------------------------------------------------------------

def init_mlstm(kg: KeyGen, cfg: ArchConfig) -> Tuple[Dict, Dict]:
    d = cfg.d_model
    h, hd = cfg.n_heads, cfg.d_model // cfg.n_heads
    p = {
        "wq": _init(kg(), (d, h, hd), cfg.dtype),
        "wk": _init(kg(), (d, h, hd), cfg.dtype),
        "wv": _init(kg(), (d, h, hd), cfg.dtype),
        "wif": _init(kg(), (d, 2 * h), cfg.dtype),  # input+forget gates
        "wo": _init(kg(), (h, hd, d), cfg.dtype),
        "ln": jnp.zeros((d,), cfg.dtype),
    }
    s = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "heads", "head_dim"),
        "wv": ("embed", "heads", "head_dim"),
        "wif": ("embed", "heads"),
        "wo": ("heads", "head_dim", "embed"),
        "ln": ("embed",),
    }
    return p, s


def apply_mlstm(p, x, cfg: ArchConfig, state: Optional[Dict] = None):
    """Matrix-memory LSTM: per head a (hd × hd) outer-product memory with
    scalar input/forget gates; parallel (attention-like) form in training,
    recurrent form in decode."""
    b, s, d = x.shape
    h = cfg.n_heads
    hd = d // h
    xin = rmsnorm(x, p["ln"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", xin, p["wq"]) / math.sqrt(hd)
    k = jnp.einsum("bsd,dhk->bshk", xin, p["wk"]) / math.sqrt(hd)
    v = jnp.einsum("bsd,dhk->bshk", xin, p["wv"])
    gates = jnp.einsum("bsd,dg->bsg", xin, p["wif"]).astype(jnp.float32)
    i_gate = jnp.exp(jnp.minimum(gates[..., :h], 8.0))       # stabilized
    f_gate = jax.nn.sigmoid(gates[..., h:])

    if state is None:
        # parallel form: D[t,τ] = (∏_{j=τ+1..t} f_j) · i_τ  (τ ≤ t)
        logf = jnp.log(f_gate + 1e-8)                        # (B,S,H)
        cum = jnp.cumsum(logf, axis=1)
        decay = cum[:, :, None, :] - cum[:, None, :, :]      # (B,t,τ,H)
        causal = jnp.tril(jnp.ones((s, s), bool))
        dmat = jnp.where(causal[None, :, :, None],
                         jnp.exp(decay) * i_gate[:, None], 0.0)
        scores = jnp.einsum("bthk,bshk->bths", q, k).astype(jnp.float32)
        scores = scores * jnp.moveaxis(dmat, 3, 2)           # (B,t,H,τ)
        norm = jnp.maximum(jnp.abs(scores.sum(-1)), 1.0)
        out = jnp.einsum("bths,bshk->bthk",
                         (scores / norm[..., None]).astype(x.dtype), v)
        new_state = None
    else:
        # recurrent: C_t = f C_{t-1} + i (v ⊗ k); y = C_t q / max(|n·q|,1)
        C, nvec = state["C"], state["n"]
        f1 = f_gate[:, 0, :, None, None]
        i1 = i_gate[:, 0, :, None, None]
        C = f1 * C + i1 * jnp.einsum("bhk,bhl->bhkl",
                                     v[:, 0].astype(jnp.float32),
                                     k[:, 0].astype(jnp.float32))
        nvec = f_gate[:, 0, :, None] * nvec \
            + i_gate[:, 0, :, None] * k[:, 0].astype(jnp.float32)
        num = jnp.einsum("bhkl,bhl->bhk", C, q[:, 0].astype(jnp.float32))
        den = jnp.maximum(
            jnp.abs(jnp.einsum("bhl,bhl->bh", nvec,
                               q[:, 0].astype(jnp.float32))), 1.0)
        out = (num / den[..., None])[:, None].astype(x.dtype)
        new_state = {"C": C, "n": nvec}

    # head-wise normalization (xLSTM applies GroupNorm before out-proj)
    out32 = out.astype(jnp.float32)
    var = jnp.mean(jnp.square(out32), axis=-1, keepdims=True)
    out = (out32 * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return x + y.astype(x.dtype), new_state


def init_slstm(kg: KeyGen, cfg: ArchConfig) -> Tuple[Dict, Dict]:
    d = cfg.d_model
    p = {
        "wx": _init(kg(), (d, 4 * d), cfg.dtype),   # i, f, z, o pre-acts
        "wh": _init(kg(), (d, 4 * d), cfg.dtype),
        "ln": jnp.zeros((d,), cfg.dtype),
    }
    s = {"wx": ("embed", "inner"), "wh": ("embed", "inner"),
         "ln": ("embed",)}
    return p, s


def apply_slstm(p, x, cfg: ArchConfig, state: Optional[Dict] = None):
    """Scalar-memory LSTM with exponential gating; lax.scan over seq."""
    b, s, d = x.shape
    xin = rmsnorm(x, p["ln"], cfg.norm_eps)
    pre_x = jnp.einsum("bsd,dg->bsg", xin, p["wx"]).astype(jnp.float32)

    def step(carry, xt):
        h_prev, c_prev, n_prev = carry
        pre = xt + h_prev @ p["wh"].astype(jnp.float32)
        i, f, z, o = jnp.split(pre, 4, axis=-1)
        i = jnp.exp(jnp.minimum(i, 8.0))
        f = jax.nn.sigmoid(f)
        c = f * c_prev + i * jnp.tanh(z)
        n = f * n_prev + i
        h = jax.nn.sigmoid(o) * c / jnp.maximum(n, 1.0)
        return (h, c, n), h

    if state is None:
        h0 = jnp.zeros((b, d), jnp.float32)
        init = (h0, h0, jnp.ones((b, d), jnp.float32))
        (_, _, _), hs = jax.lax.scan(step, init,
                                     jnp.moveaxis(pre_x, 1, 0))
        y = jnp.moveaxis(hs, 0, 1)
        new_state = None
    else:
        carry = (state["h"], state["c"], state["n"])
        carry, h = step(carry, pre_x[:, 0])
        y = h[:, None]
        new_state = {"h": carry[0], "c": carry[1], "n": carry[2]}

    # feature-wise normalization (GroupNorm analogue)
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    out = (y * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype)
    return x + out, new_state


def init_xlstm_state(cfg: ArchConfig, kind: str, batch: int) -> Dict:
    d = cfg.d_model
    h, hd = cfg.n_heads, cfg.d_model // cfg.n_heads
    if kind == "mlstm":
        return {"C": jnp.zeros((batch, h, hd, hd), jnp.float32),
                "n": jnp.zeros((batch, h, hd), jnp.float32)}
    return {"h": jnp.zeros((batch, d), jnp.float32),
            "c": jnp.zeros((batch, d), jnp.float32),
            "n": jnp.ones((batch, d), jnp.float32)}
