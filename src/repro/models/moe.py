"""Mixture-of-Experts FFN with token-choice top-k routing.

Routing, as OLMoE, Qwen2-MoE and Jamba (``JambaSparseMoeBlock``) publish
it: a softmax over all router logits in float32, the top-k of those
probabilities as the combine weights, no renormalisation.

The layer is told which experts it holds (``cfg.experts_held``, a range
[lo, hi); all of them by default): expert parallelism. It routes over all
``n_experts`` with the published router width and computes only its own
experts' part of the output; that partial result is what goes on, as it
would on each chip of an expert-parallel deployment before the exchange.
The held layer is dropless: each token's assignments to held experts are
sorted by expert and run through grouped matmuls
(``jax.lax.ragged_dot``), which read each held expert's weights once —
in decode as in prefill — and never drop a token.

The capacity scheme stays for the sharded dry run (``launch/dryrun``
sets ``cfg.moe_expert_axes``), where GSPMD has to partition the expert
dimension: per-token expert assignments are flattened, positions within
each expert computed by a cumulative sum over the (tokens·topk, experts)
one-hot, tokens beyond capacity dropped, and activations scattered into
an (experts·capacity, d) buffer that is batch-matmul'd against stacked
expert weights. This keeps every shape static (compile-friendly at 512
devices) without materializing the (tokens, experts, capacity) dispatch
tensor.

Expert weights carry the 'experts' logical axis → the planner shards them
over the `model` mesh axis (expert parallelism); the scatter/gather across
the (data-sharded) token axis and (model-sharded) expert axis is where
GSPMD inserts the all-to-all — the MoE collective the roofline analysis
tracks. Experts are padded up to a multiple of the mesh axis when the
config's count is indivisible (qwen2-moe: 60 → 64), with router logits of
padded experts masked to -inf (a legality-branch resolution, DESIGN.md §4).

Both return ``(x + y, stats)``: ``expert_counts`` (assignments each held
expert computed), ``assign_held``, ``assign_total`` and ``dropped``.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ArchConfig, KeyGen, _act, _init, rmsnorm


def padded_experts(cfg: ArchConfig, tp: int = 16) -> int:
    e = cfg.n_experts
    return ((e + tp - 1) // tp) * tp


def init_moe(kg: KeyGen, cfg: ArchConfig, tp: int = 16
             ) -> Tuple[Dict, Dict]:
    d = cfg.d_model
    f = cfg.expert_d_ff or cfg.d_ff
    e_pad = padded_experts(cfg, tp)
    lo, hi = cfg.experts_held or (0, e_pad)
    gated = cfg.mlp_act in ("silu", "gelu")
    # each expert's matrices at std 1/sqrt(fan-in) of that expert
    p = {
        "router": _init(kg(), (d, e_pad), jnp.float32),
        "wi": _init(kg(), (hi - lo, d, f), cfg.dtype, scale=d ** -0.5),
        "wo": _init(kg(), (hi - lo, f, d), cfg.dtype, scale=f ** -0.5),
        "ln": jnp.zeros((d,), cfg.dtype),
    }
    s = {
        "router": ("embed", "experts"),
        "wi": ("experts", "embed", "mlp"),
        "wo": ("experts", "mlp", "embed"),
        "ln": ("embed",),
    }
    if gated:
        p["wg"] = _init(kg(), (hi - lo, d, f), cfg.dtype, scale=d ** -0.5)
        s["wg"] = ("experts", "embed", "mlp")
    if cfg.n_shared_experts:
        p["shared_wi"] = _init(kg(), (d, f * cfg.n_shared_experts),
                               cfg.dtype)
        p["shared_wo"] = _init(kg(), (f * cfg.n_shared_experts, d),
                               cfg.dtype)
        s["shared_wi"] = ("embed", "mlp")
        s["shared_wo"] = ("mlp", "embed")
        if gated:
            p["shared_wg"] = _init(kg(), (d, f * cfg.n_shared_experts),
                                   cfg.dtype)
            s["shared_wg"] = ("embed", "mlp")
    return p, s


def _apply_moe_capacity(p, x, cfg: ArchConfig, ht, weights, expert_ids):
    """The capacity scheme (sharded dry run): every expert, fixed
    capacity, overflow dropped."""
    b, s, d = x.shape
    t = b * s
    e_pad = p["router"].shape[1]
    k = cfg.experts_topk
    act = _act(cfg.mlp_act)
    weights = weights.astype(x.dtype)

    # --- capacity + position within expert -----------------------------
    cap = int(max(1, (t * k // e_pad) * cfg.capacity_factor))
    flat_e = expert_ids.reshape(-1)                         # (T*K,)
    onehot = jax.nn.one_hot(flat_e, e_pad, dtype=jnp.int32)  # (T*K, E)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - 1)             # running count
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None],
                              axis=1)[:, 0]                 # (T*K,)
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, e_pad * cap)  # overflow slot

    # --- dispatch -------------------------------------------------------
    xe = jnp.repeat(ht, k, axis=0)                          # (T*K, D)
    buf = jnp.zeros((e_pad * cap + 1, d), x.dtype).at[slot].add(xe)
    buf = buf[:-1].reshape(e_pad, cap, d)

    def _anchor(t):
        """Pin (expert, capacity) dims to the planner's axes: without
        this, GSPMD propagation can leave the expert einsums replicated
        over idle mesh axes (a silent 16× compute waste). The capacity
        dim takes the axes the expert count cannot cover."""
        if not cfg.moe_expert_axes:
            return t
        try:
            from jax.sharding import PartitionSpec as P_

            ax = tuple(cfg.moe_expert_axes)
            e_spec = ax if len(ax) > 1 else ax[0]
            c_ax = tuple(cfg.moe_capacity_axes or ())
            c_spec = (c_ax if len(c_ax) > 1 else c_ax[0]) if c_ax \
                else P_.UNCONSTRAINED
            spec = [e_spec, c_spec] + [P_.UNCONSTRAINED] * (t.ndim - 2)
            return jax.lax.with_sharding_constraint(t, P_(*spec))
        except Exception:
            return t

    buf = _anchor(buf)

    # --- expert computation (batched over experts) -----------------------
    up = jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    if "wg" in p:
        gate = jnp.einsum("ecd,edf->ecf", buf, p["wg"])
        up = act(gate) * up
    else:
        up = act(up)
    out = _anchor(jnp.einsum("ecf,efd->ecd", up, p["wo"]))  # (E, C, D)

    # --- combine ----------------------------------------------------------
    out_flat = out.reshape(e_pad * cap, d)
    out_flat = jnp.concatenate(
        [out_flat, jnp.zeros((1, d), out_flat.dtype)], axis=0)
    gathered = out_flat[slot]                               # (T*K, D)
    gathered = gathered * weights.reshape(-1)[:, None]
    y = gathered.reshape(t, k, d).sum(axis=1)
    counts = onehot.sum(axis=0)
    stats = {"expert_counts": counts, "assign_held": counts.sum(),
             "assign_total": jnp.int32(t * k),
             "dropped": (~keep).sum().astype(jnp.int32)}
    return y, stats


def route(ht, router, cfg: ArchConfig):
    """Softmax over all router logits in float32, then the top-k of
    the probabilities as combine weights (no renormalisation).
    ht: (T, D). Returns (weights (T, K) f32, expert ids (T, K))."""
    e_pad = router.shape[1]
    logits = ht.astype(jnp.float32) @ router.astype(jnp.float32)
    if e_pad != cfg.n_experts:
        pad_mask = jnp.arange(e_pad) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, :], -jnp.inf, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    return jax.lax.top_k(probs, cfg.experts_topk)


def _apply_moe_held(p, ht, cfg: ArchConfig, weights, expert_ids, tok_ok):
    """The dropless layer over the held experts: sort the held
    assignments by expert, grouped matmuls, unsort, combine."""
    t, d = ht.shape
    k = cfg.experts_topk
    e_held = p["wi"].shape[0]
    lo = cfg.held_range[0]
    act = _act(cfg.mlp_act)
    local = expert_ids - lo
    held = (local >= 0) & (local < e_held)
    if tok_ok is not None:
        held = held & tok_ok[:, None]
    # assignments elsewhere (other chips' experts, padding) sort last,
    # into a group of their own that no matmul reads
    group = jnp.where(held, local, e_held).reshape(-1)        # (T*K,)
    order = jnp.argsort(group, stable=True)
    counts = jnp.bincount(group, length=e_held + 1)[:e_held]
    counts = counts.astype(jnp.int32)     # the chip's kernel takes int32
    rows = ht[order // k]                                      # (T*K, D)
    up = jax.lax.ragged_dot(rows, p["wi"], counts)
    if "wg" in p:
        up = act(jax.lax.ragged_dot(rows, p["wg"], counts)) * up
    else:
        up = act(up)
    out = jax.lax.ragged_dot(up.astype(ht.dtype), p["wo"], counts)
    # back to token order; rows of no held expert give nothing
    out = out[jnp.argsort(order)].reshape(t, k, d)
    w = jnp.where(held, weights, 0.0)
    y = jnp.where(held[..., None], out.astype(jnp.float32), 0.0)
    y = (y * w[..., None]).sum(axis=1)
    assign_held = counts.sum()
    stats = {"expert_counts": counts, "assign_held": assign_held,
             "assign_total": (jnp.int32(t * k) if tok_ok is None
                              else tok_ok.sum().astype(jnp.int32) * k),
             "dropped": held.sum().astype(jnp.int32) - assign_held}
    return y, stats


def apply_moe(p, x, cfg: ArchConfig, valid=None):
    """x: (B, S, D) → ((B, S, D) residual-added, stats). valid: (B,)
    count of real tokens at the front of each row; padding after them
    is routed nowhere."""
    b, s, d = x.shape
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    ht = h.reshape(b * s, d)
    weights, expert_ids = route(ht, p["router"], cfg)
    if cfg.moe_expert_axes:
        y, stats = _apply_moe_capacity(p, x, cfg, ht, weights, expert_ids)
    else:
        tok_ok = None if valid is None else (
            jnp.arange(s)[None, :] < valid[:, None]).reshape(-1)
        y, stats = _apply_moe_held(p, ht, cfg, weights, expert_ids,
                                   tok_ok)

    # --- shared experts (dense, on every chip alike) ----------------------
    if "shared_wi" in p:
        act = _act(cfg.mlp_act)
        up_s = jnp.einsum("td,df->tf", ht, p["shared_wi"])
        if "shared_wg" in p:
            up_s = act(jnp.einsum("td,df->tf", ht, p["shared_wg"])) * up_s
        else:
            up_s = act(up_s)
        y = y + jnp.einsum("tf,fd->td", up_s, p["shared_wo"])

    return x + y.reshape(b, s, d).astype(x.dtype), stats
