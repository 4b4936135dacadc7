"""Plain reference of the Jamba2-Mini cut, its seeded weights, the
control, and the work of a decode tick.

Imports nothing of the program. The configuration is the benchmark's
JSON file (the catalog's keys). The weights are drawn here, from the
seed, tensor by tensor with one jitted draw each: the benchmark's
system hands the same bfloat16 tensors to the program, and the
reference draws them again after the program's state is freed and
upcasts them to float32. Then it runs a plain ``jax.numpy`` forward
over one whole sequence in float32 at ``"highest"`` matmul precision,
layer by layer (each layer's weights drawn, used and dropped), with
attention in blocks of queries:

    layer i:  x += mixer_i(RMSNorm(x));  x += ffn_i(RMSNorm(x))
    mixer:    attention (GQA, causal, no positional encoding) at
              i % attn_layer_period == attn_layer_offset; else Mamba-1:
              (x, z) = in_proj; x = silu(conv1d(x) + b);
              (Δ, B, C) = RMSNorm each of x_proj(x);
              Δ = softplus(dt_proj(Δ) + dt_bias); A = -exp(A_log);
              s_t = exp(Δ_t A) s_{t-1} + Δ_t x_t B_t; y = s_t C_t + D x_t;
              out_proj(y · silu(z))
    ffn:      MoE at i % expert_layer_period == expert_layer_offset
              (softmax over all router_experts logits in float32, top
              num_experts_per_tok, no renormalisation; only the held
              experts' part), else a dense SiLU-gated MLP
    logits  = unembed(RMSNorm(x)) over the vocabulary slice

RMSNorm scales are stored as ``1 + scale`` (zero = unit weight), as the
program stores them. The control is the same forward with every
projection, expert and output-head matrix rounded through float8 e4m3
with a per-tensor absolute-maximum scale.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

Q_BLOCK = 512          # attention queries per block
FP8_MAX = 240.0        # largest finite value of reduce_precision(4, 3)


@dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv: int
    hd: int
    inner: int
    n: int
    r: int
    k: int
    f: int
    layers: int
    period: int
    attn_offset: int
    moe_period: int
    moe_offset: int
    held: Tuple[int, int]
    router: int
    topk: int
    vocab: int
    eps: float

    @property
    def periods(self) -> int:
        return self.layers // self.period

    def mixer(self, i: int) -> str:
        return "attn" if i % self.period == self.attn_offset else "mamba"

    def ffn(self, i: int) -> str:
        return "moe" if i % self.moe_period == self.moe_offset else "mlp"


def dims(cfg: dict) -> Dims:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    held = tuple(cfg["experts_held"])
    assert held[1] - held[0] == cfg["num_experts"], cfg
    assert cfg["num_hidden_layers"] % cfg["attn_layer_period"] == 0
    return Dims(d=d, heads=h, kv=cfg["num_key_value_heads"], hd=d // h,
                inner=cfg["mamba_expand"] * d, n=cfg["mamba_d_state"],
                r=cfg["mamba_dt_rank"], k=cfg["mamba_d_conv"],
                f=cfg["intermediate_size"],
                layers=cfg["num_hidden_layers"],
                period=cfg["attn_layer_period"],
                attn_offset=cfg["attn_layer_offset"],
                moe_period=cfg["expert_layer_period"],
                moe_offset=cfg["expert_layer_offset"], held=held,
                router=cfg["router_experts"],
                topk=cfg["num_experts_per_tok"], vocab=cfg["vocab_size"],
                eps=cfg["rms_norm_eps"])


# -- the seeded weights --------------------------------------------------------
#
# name → (shape, how it is drawn, scale). Shapes of layer tensors are
# those of one layer; they are drawn stacked over the periods, (P, ...),
# for layer index i within the period. Projections: normal, std
# 1/sqrt(fan-in). Mamba's own init for the recurrence: conv uniform in
# ±1/sqrt(width), dt_proj uniform in ±dt_rank^-1/2, dt_bias the inverse
# softplus of Δ0 log-uniform in [1e-3, 1e-1], A_log = log(1..N), D = 1.

def layer_specs(dm: Dims, i: int) -> Dict[str, tuple]:
    d, inner, f = dm.d, dm.inner, dm.f
    e = dm.held[1] - dm.held[0]
    out: Dict[str, tuple] = {}
    if dm.mixer(i) == "attn":
        out.update({
            "attn.ln": ((d,), "zeros", 0.0),
            "attn.wq": ((d, dm.heads, dm.hd), "normal", d ** -0.5),
            "attn.wk": ((d, dm.kv, dm.hd), "normal", d ** -0.5),
            "attn.wv": ((d, dm.kv, dm.hd), "normal", d ** -0.5),
            "attn.wo": ((dm.heads, dm.hd, d), "normal",
                        (dm.heads * dm.hd) ** -0.5)})
    else:
        out.update({
            "mamba.ln": ((d,), "zeros", 0.0),
            "mamba.in_proj": ((d, 2 * inner), "normal", d ** -0.5),
            "mamba.conv_w": ((dm.k, inner), "uniform", dm.k ** -0.5),
            "mamba.conv_b": ((inner,), "uniform", dm.k ** -0.5),
            "mamba.x_proj": ((inner, dm.r + 2 * dm.n), "normal",
                             inner ** -0.5),
            "mamba.dt_ln": ((dm.r,), "zeros", 0.0),
            "mamba.b_ln": ((dm.n,), "zeros", 0.0),
            "mamba.c_ln": ((dm.n,), "zeros", 0.0),
            "mamba.dt_proj": ((dm.r, inner), "uniform", dm.r ** -0.5),
            "mamba.dt_bias": ((inner,), "dt_bias", 0.0),
            "mamba.a_log": ((inner, dm.n), "a_log", 0.0),
            "mamba.d_skip": ((inner,), "ones", 0.0),
            "mamba.out_proj": ((inner, d), "normal", inner ** -0.5)})
    if dm.ffn(i) == "moe":
        out.update({
            "moe.ln": ((d,), "zeros", 0.0),
            "moe.router": ((d, dm.router), "normal", d ** -0.5),
            "moe.wi": ((e, d, f), "normal", d ** -0.5),
            "moe.wg": ((e, d, f), "normal", d ** -0.5),
            "moe.wo": ((e, f, d), "normal", f ** -0.5)})
    else:
        out.update({
            "mlp.ln": ((d,), "zeros", 0.0),
            "mlp.wi": ((d, f), "normal", d ** -0.5),
            "mlp.wg": ((d, f), "normal", d ** -0.5),
            "mlp.wo": ((f, d), "normal", f ** -0.5)})
    return out


def global_specs(dm: Dims) -> Dict[str, tuple]:
    return {"embed": ((dm.vocab, dm.d), "normal", 1.0),
            "unembed": ((dm.vocab, dm.d), "normal", dm.d ** -0.5),
            "final_ln": ((dm.d,), "zeros", 0.0)}


# float32 where the program keeps them in float32; the rest bfloat16
F32_NAMES = ("mamba.dt_bias", "mamba.a_log", "mamba.d_skip", "moe.router")


def _jax():
    import jax
    import jax.numpy as jnp

    return jax, jnp


_JIT: dict = {}


def _key(seed: int, *words: int):
    import jax

    seed = int(seed) % (1 << 64)
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    for w in words:
        key = jax.random.fold_in(key, w)
    return key


def _draw_impl(key, shape, kind, scale, dtype):
    jax, jnp = _jax()

    f32 = jnp.float32
    if kind == "normal":
        t = jax.random.normal(key, shape, f32) * scale
    elif kind == "uniform":
        t = jax.random.uniform(key, shape, f32, -scale, scale)
    elif kind == "zeros":
        t = jnp.zeros(shape, f32)
    elif kind == "ones":
        t = jnp.ones(shape, f32)
    elif kind == "a_log":
        t = jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1,
                                                dtype=f32)), shape)
    elif kind == "dt_bias":
        dt0 = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                         math.log(1e-1)))
        dt0 = jnp.maximum(dt0, 1e-4)
        t = dt0 + jnp.log(-jnp.expm1(-dt0))
    else:
        raise ValueError(kind)
    return t.astype(dtype)


def draw(seed: int, dm: Dims, layer_in_period: int, name: str):
    """One weight tensor, bfloat16 (float32 for ``F32_NAMES``), stacked
    over the periods: (P,) + its shape. ``layer_in_period`` -1 draws a
    global tensor (embedding, output head, final norm), unstacked."""
    import jax.numpy as jnp

    if layer_in_period < 0:
        shape, kind, scale = global_specs(dm)[name]
        names = list(global_specs(dm))
    else:
        shape, kind, scale = layer_specs(dm, layer_in_period)[name]
        shape = (dm.periods,) + shape
        names = sorted(layer_specs(dm, layer_in_period))
    dtype = jnp.float32 if name in F32_NAMES else jnp.bfloat16
    key = _key(seed, layer_in_period + 1, names.index(name))
    if "draw" not in _JIT:
        import jax

        _JIT["draw"] = jax.jit(_draw_impl, static_argnums=(1, 2, 3, 4))
    return _JIT["draw"](key, shape, kind, float(scale), dtype)


# -- the forward ---------------------------------------------------------------

def round_fp8(t):
    """``t`` rounded through float8 e4m3 at a per-tensor scale that
    maps its largest magnitude to the format's largest value."""
    jax, jnp = _jax()
    amax = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30)
    s = FP8_MAX / amax
    return jax.lax.reduce_precision(t * s, exponent_bits=4,
                                    mantissa_bits=3) / s


# matrices the control rounds through float8
FP8_NAMES = ("attn.wq", "attn.wk", "attn.wv", "attn.wo", "mamba.in_proj",
             "mamba.x_proj", "mamba.dt_proj", "mamba.out_proj", "mlp.wi",
             "mlp.wg", "mlp.wo", "moe.wi", "moe.wg", "moe.wo", "unembed")


def _rms(x, scale, eps):
    jax, jnp = _jax()
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _mamba(x, w, dm: Dims):
    jax, jnp = _jax()
    s = x.shape[0]
    h = _rms(x, w["mamba.ln"], dm.eps)
    xs, z = jnp.split(h @ w["mamba.in_proj"], 2, axis=-1)
    xc = jnp.concatenate([jnp.zeros((dm.k - 1, dm.inner), xs.dtype), xs])
    conv = w["mamba.conv_b"] + sum(xc[j:j + s] * w["mamba.conv_w"][j]
                                   for j in range(dm.k))
    u = jax.nn.silu(conv)
    dt_in, bm, cm = jnp.split(u @ w["mamba.x_proj"], [dm.r, dm.r + dm.n],
                              axis=-1)
    dt_in = _rms(dt_in, w["mamba.dt_ln"], dm.eps)
    bm = _rms(bm, w["mamba.b_ln"], dm.eps)
    cm = _rms(cm, w["mamba.c_ln"], dm.eps)
    dt = jax.nn.softplus(dt_in @ w["mamba.dt_proj"] + w["mamba.dt_bias"])
    a = -jnp.exp(w["mamba.a_log"])

    def step(state, inp):
        u_t, dt_t, b_t, c_t = inp
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return state, state @ c_t

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, x.dtype), (u, dt, bm, cm))
    y = (y + w["mamba.d_skip"] * u) * jax.nn.silu(z)
    return x + y @ w["mamba.out_proj"]


def _attn(x, w, dm: Dims):
    jax, jnp = _jax()
    s = x.shape[0]
    h = _rms(x, w["attn.ln"], dm.eps)
    q = jnp.einsum("sd,dhk->shk", h, w["attn.wq"])
    k = jnp.einsum("sd,dhk->shk", h, w["attn.wk"])
    v = jnp.einsum("sd,dhk->shk", h, w["attn.wv"])
    g = dm.heads // dm.kv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    outs = []
    for q0 in range(0, s, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        sc = jnp.einsum("qhk,shk->hqs", qb, k) / math.sqrt(dm.hd)
        causal = (jnp.arange(s)[None, :]
                  <= (q0 + jnp.arange(qb.shape[0]))[:, None])
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqs,shk->qhk", p, v))
    out = jnp.concatenate(outs)
    return x + jnp.einsum("qhk,hkd->qd", out, w["attn.wo"])


def _mlp(h, wi, wg, wo):
    jax, _ = _jax()
    return (jax.nn.silu(h @ wg) * (h @ wi)) @ wo


def _dense_ffn(x, w, dm: Dims):
    h = _rms(x, w["mlp.ln"], dm.eps)
    return x + _mlp(h, w["mlp.wi"], w["mlp.wg"], w["mlp.wo"])


def _route(x, w, dm: Dims):
    """Normed input, each held expert's combine weight (S, E), and each
    token's routing margin: the gap between the router logits of the
    last expert chosen and the first one left out."""
    jax, jnp = _jax()
    h = _rms(x, w["moe.ln"], dm.eps)
    logits = h @ w["moe.router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, dm.topk)
    ids = jnp.arange(dm.held[0], dm.held[1])
    weight = jnp.where(top_i[:, :, None] == ids, top_w[:, :, None],
                       0.0).sum(1)
    top_l = jax.lax.top_k(logits, dm.topk + 1)[0]
    return h, weight, top_l[:, -2] - top_l[:, -1]


def _expert(h, weight, wi, wg, wo):
    return weight[:, None] * _mlp(h, wi, wg, wo)


def _jitted(name: str):
    """One jit per sublayer kind; the weights are arguments."""
    if "mamba" not in _JIT:
        jax, _ = _jax()
        _JIT.update(mamba=jax.jit(_mamba, static_argnums=2),
                    attn=jax.jit(_attn, static_argnums=2),
                    mlp=jax.jit(_dense_ffn, static_argnums=2),
                    route=jax.jit(_route, static_argnums=2),
                    expert=jax.jit(_expert))
    return _JIT[name]


def _layer_weights(seed: int, dm: Dims, i: int, per: int, prefix: str,
                   fp8: bool) -> dict:
    _, jnp = _jax()
    out = {}
    for name in layer_specs(dm, i):
        if not name.startswith(prefix) or name in ("moe.wi", "moe.wg",
                                                   "moe.wo"):
            continue
        t = draw(seed, dm, i, name)[per].astype(jnp.float32)
        out[name] = round_fp8(t) if fp8 and name in FP8_NAMES else t
    return out


def forward(seed: int, cfg: dict, tokens: Sequence[int],
            positions: Sequence[int], *, fp8: bool = False):
    """Logits (len(positions), vocab) of one sequence at ``positions``,
    float32, and every position's smallest routing margin over the MoE
    layers; ``fp8`` gives the control's."""
    jax, jnp = _jax()
    dm = dims(cfg)
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        emb = draw(seed, dm, -1, "embed")
        x = emb[jnp.asarray(np.asarray(tokens, np.int32))].astype(f32)
        del emb
        margin = jnp.full((x.shape[0],), jnp.inf, f32)
        for layer in range(dm.layers):
            i, per = layer % dm.period, layer // dm.period
            kind = dm.mixer(i)
            x = _jitted(kind)(x, _layer_weights(seed, dm, i, per, kind,
                                                fp8), dm)
            if dm.ffn(i) == "mlp":
                x = _jitted("mlp")(x, _layer_weights(seed, dm, i, per,
                                                     "mlp", fp8), dm)
                continue
            h, weight, gap = _jitted("route")(
                x, _layer_weights(seed, dm, i, per, "moe", fp8), dm)
            margin = jnp.minimum(margin, gap)
            mats = [draw(seed, dm, i, n)[per] for n in ("moe.wi", "moe.wg",
                                                        "moe.wo")]
            y = jnp.zeros_like(x)
            for e in range(dm.held[1] - dm.held[0]):
                we = [m[e].astype(f32) for m in mats]
                if fp8:
                    we = [round_fp8(t) for t in we]
                y = y + _jitted("expert")(h, weight[:, e], *we)
            x = x + y
            del mats
        idx = jnp.asarray(np.asarray(positions, np.int32))
        head = draw(seed, dm, -1, "unembed").astype(f32)
        if fp8:
            head = round_fp8(head)
        h = _rms(x[idx], draw(seed, dm, -1, "final_ln").astype(f32),
                 dm.eps)
        logits = h @ head.T
    return np.asarray(logits), np.asarray(margin)


def logits_err(got: np.ndarray, want: np.ndarray) -> float:
    """Normwise relative error of one slot's logits at one step."""
    return float(np.linalg.norm(got.astype(np.float64) - want)
                 / np.linalg.norm(want.astype(np.float64)))


# -- the work of a decode tick -------------------------------------------------

def weight_bytes(dm: Dims) -> Dict[str, float]:
    """Bytes of the weights by part: ``expert`` (one routed expert's
    three matrices), ``experts`` (all the held ones), ``embed`` (the
    embedding table) and ``other`` (everything else: mixers, dense
    MLPs, routers, norms, the output head)."""
    total, experts = 0.0, 0.0
    for i in range(dm.period):
        for name, (shape, _, _) in layer_specs(dm, i).items():
            b = float(np.prod(shape)) * (4 if name in F32_NAMES else 2) \
                * dm.periods
            total += b
            if name in ("moe.wi", "moe.wg", "moe.wo"):
                experts += b
    embed = 2.0 * dm.vocab * dm.d
    total += 2 * embed + 2.0 * dm.d
    return {"expert": 3 * 2.0 * dm.d * dm.f, "experts": experts,
            "embed": embed, "other": total - experts - embed}


def per_sequence_bytes(dm: Dims) -> Dict[str, float]:
    """KV bytes per position (every attention layer, K and V, bf16) and
    recurrent state bytes (every Mamba layer: the last d_conv-1 conv
    inputs in bf16 and the (inner, N) state in float32)."""
    n_attn = sum(dm.mixer(i) == "attn" for i in range(dm.layers))
    n_mamba = dm.layers - n_attn
    return {"kv_per_token": n_attn * 2 * dm.kv * dm.hd * 2.0,
            "state": n_mamba * ((dm.k - 1) * dm.inner * 2.0
                                + dm.inner * dm.n * 4.0)}


def decode_work(dm: Dims, ticks: int, slots_ticks: int, context: int,
                experts_hit: int, assigned: int) -> Tuple[float, float]:
    """Operations and compulsory bytes of ``ticks`` decode ticks:
    ``slots_ticks`` tokens decoded in all, over contexts that sum to
    ``context`` positions, with ``experts_hit`` (layer, held expert)
    pairs that computed a token and ``assigned`` token-expert
    assignments on held experts. Bytes: the non-expert weights once a
    tick, one embedding row a token, each expert hit once, the KV at
    the actual lengths, and each slot's recurrent state read and
    written. Operations: two a weight a token, two a weight of each
    assignment, and attention's ``4 · heads · head_dim`` a position of
    context."""
    wb = weight_bytes(dm)
    seq = per_sequence_bytes(dm)
    n_attn = seq["kv_per_token"] / (2 * dm.kv * dm.hd * 2.0)
    nbytes = (ticks * wb["other"] + slots_ticks * dm.d * 2.0
              + experts_hit * wb["expert"]
              + context * seq["kv_per_token"]
              + 2 * slots_ticks * seq["state"])
    flops = (2 * slots_ticks * wb["other"] / 2.0
             + 2 * assigned * wb["expert"] / 2.0
             + 4.0 * context * dm.heads * dm.hd * n_attn)
    return flops, nbytes
