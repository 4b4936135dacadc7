"""Jamba2-Mini's block against the plain reference
(``repro.models.reference_jamba``), on the CPU at the smoke widths with
seeded random weights.

The comparisons run in float32, so that they test the equations and
not a precision: tolerances are normwise relative errors of the logits
(or of the MoE layer's output), and the program and the reference
compute the same equations in different orders (a blocked associative
scan against a step-by-step one, grouped matmuls against one expert at
a time), so they agree to float32 rounding, about 1e-6; the limit is
1e-4 (1e-5 where both sides are the program's own code). The benchmark
compares the bfloat16 program at the published widths.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import moe
from repro.models import reference_jamba as R
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine, lm_steps


def _cfg(dtype=jnp.float32, **kw):
    return dataclasses.replace(get_smoke_config("jamba2_mini"),
                               dtype=dtype, **kw)


def _err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_published_values():
    cfg = get_config("jamba2_mini")
    assert (cfg.layers, cfg.d_model, cfg.n_heads, cfg.kv_heads,
            cfg.head_dim, cfg.vocab) == (32, 4096, 32, 8, 128, 65536)
    assert (cfg.d_ff, cfg.expert_d_ff, cfg.n_experts,
            cfg.experts_topk) == (14336, 14336, 16, 2)
    assert (cfg.ssm_state, cfg.ssm_conv, cfg.ssm_expand,
            cfg.dt_rank) == (16, 4, 2, 256)
    assert [cfg.layer_kind(i) for i in range(8)] == \
        ["mamba"] * 4 + ["attn"] + ["mamba"] * 3
    assert [cfg.layer_is_moe(i) for i in range(8)] == [False, True] * 4
    assert not cfg.use_rope and not cfg.tie_embeddings
    assert get_config("jamba_1_5_large_398b").dt_rank == 512
    from repro.configs.jamba2_mini import ep_config

    cut = ep_config()
    assert (cut.layers, cut.experts_held, cut.vocab) == (8, (0, 8), 8192)
    assert cut.param_count() == 7_188_258_816


@pytest.mark.parametrize("arch", ["jamba2_mini", "jamba_1_5_large_398b"])
def test_forward_matches_reference(arch):
    cfg = dataclasses.replace(get_smoke_config(arch), dtype=jnp.float32)
    params, _ = T.init_params(cfg, jax.random.key(0))
    toks = np.random.default_rng(0).integers(0, cfg.vocab, 40)
    @jax.jit
    def program(params, toks):
        x = T.embed_tokens(params, toks[None], cfg)
        x, _ = T.forward(params, x, cfg, positions=jnp.arange(40))
        return T._head(params, x, cfg)[0, :, :cfg.vocab]

    got = program(params, jnp.asarray(toks))
    want = R.forward(params, toks, cfg)
    assert _err(got, want) < 1e-4


@pytest.mark.parametrize("chunk", [4, 8])
def test_chunked_prefill_then_decode_matches_reference(chunk):
    """Prompts that cross chunk boundaries, prefilled chunk by chunk
    into their slots (KV, conv and recurrent state), then decoded by
    continuous batching: every step's logits against the reference's
    full forward over prompt and generated tokens, and each first
    token against the reference's greedy choice."""
    cfg = _cfg()
    params, _ = T.init_params(cfg, jax.random.key(1))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (5, 13, 21)]
    eng = ServeEngine(params, cfg, n_slots=2, max_seq=48,
                      prefill_chunk=chunk)
    reqs = [Request(f"r{i}", p, max_tokens=6) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    seen = []
    while eng.queue or eng.active:
        before = dict(eng.active)
        eng.step()
        logits = np.asarray(eng.last_logits)
        for slot, req in before.items():
            pos = len(req.prompt) + len(req.generated) - 2
            seen.append((req, pos, logits[slot, :cfg.vocab]))
    # every request's prompt, plus the requests admitted after a slot
    # freed up (their first steps)
    assert eng.prefills == 3
    assert eng.prefill_chunks == sum(-(-len(p) // chunk) for p in prompts)
    for r in reqs:
        want = R.forward(params, np.concatenate([r.prompt, r.generated]),
                         cfg)
        assert r.generated[0] == int(np.argmax(want[len(r.prompt) - 1]))
        for req, pos, got in seen:
            if req is r:
                assert _err(got, want[pos]) < 1e-4, (r.request_id, pos)
    assert len(seen) == 3 * 5 - 3   # each first step runs in its admission tick
    counters = eng._mscope
    assert counters.counter("moe.dropped").value == 0
    assert counters.counter("moe.assign_total").value > 0


def _share(full, cfg, lo, hi):
    """The part of an MoE layer's params that holds experts [lo, hi)."""
    p = dict(full, **{k: full[k][lo:hi] for k in ("wi", "wg", "wo")})
    return p, dataclasses.replace(cfg, experts_held=(lo, hi))


@pytest.mark.parametrize("held", [[(0, 4), (4, 8)],
                                  [(0, 2), (2, 5), (5, 8)]],
                         ids=["halves", "uneven"])
def test_expert_shares_sum_to_uncut_layer(held):
    """Each chip's share of the layer computes its own experts' part;
    with the residual (and anything every chip computes alike) counted
    once, the shares add up to the uncut layer, and the uncut layer is
    the reference's."""
    cfg = _cfg()
    full, _ = moe.init_moe(T.KeyGen(jax.random.key(2)), cfg)
    x = jax.random.normal(jax.random.key(3), (2, 16, cfg.d_model))
    y_full, st = moe.apply_moe(full, x, cfg)
    parts = []
    for lo, hi in held:
        p, c = _share(full, cfg, lo, hi)
        parts.append(moe.apply_moe(p, x, c)[0] - x)
    assert _err(x + sum(parts), y_full) < 1e-5
    want = R.moe(full, x.reshape(-1, cfg.d_model), cfg)
    assert _err((y_full - x).reshape(-1, cfg.d_model), want) < 1e-4
    assert int(st["assign_held"]) == 2 * 16 * cfg.experts_topk
    assert int(st["dropped"]) == 0


@pytest.mark.parametrize("held", [None, (0, 4), (4, 8)],
                         ids=["all", "low", "high"])
def test_no_token_dropped_under_skewed_routing(held):
    """Every token the same: all of them go to the same two experts,
    far past any capacity. The dropless layer computes every
    assignment to a held expert; the capacity scheme drops most."""
    cfg = _cfg()
    full, _ = moe.init_moe(T.KeyGen(jax.random.key(4)), cfg)
    x = jnp.broadcast_to(jax.random.normal(jax.random.key(5),
                                           (cfg.d_model,)),
                         (1, 64, cfg.d_model))
    p, c = (full, cfg) if held is None else _share(full, cfg, *held)
    y, st = moe.apply_moe(p, x, c)
    _, ids = moe.route(x[0], full["router"], cfg)
    lo, hi = c.held_range
    n_held = int(((ids[0] >= lo) & (ids[0] < hi)).sum())
    assert int(st["dropped"]) == 0
    assert int(st["assign_held"]) == 64 * n_held
    counts = sorted(np.asarray(st["expert_counts"]).tolist())
    assert counts[len(counts) - n_held:] == [64] * n_held
    if n_held:
        assert _err((y - x)[0], R.moe(p, x[0], c)) < 1e-4
    h = x.reshape(-1, cfg.d_model)
    w, ids = moe.route(h, full["router"], cfg)
    _, cap = moe._apply_moe_capacity(full, x, cfg, h, w, ids)
    assert int(cap["dropped"]) > 0


def test_decode_leaves_idle_slots_as_they_were():
    """A row with valid 0 neither advances its KV index nor moves its
    conv or recurrent state."""
    cfg = _cfg()
    params, _ = T.init_params(cfg, jax.random.key(6))
    caches = T.init_caches(cfg, 2, 16)
    caches = jax.tree.map(lambda a: a + 1 if a.dtype != jnp.int32 else a,
                          caches)
    tok = jnp.asarray([[3], [4]], jnp.int32)
    _, new = T.decode_step(params, tok, caches, cfg,
                           valid=jnp.asarray([1, 0], jnp.int32))
    for old_c, new_c in zip(caches, new):
        if "ssm" in old_c:
            for k in ("conv", "h"):
                assert np.array_equal(old_c["ssm"][k][:, 1],
                                      new_c["ssm"][k][:, 1])
                assert not np.array_equal(old_c["ssm"][k][:, 0],
                                          new_c["ssm"][k][:, 0])
        else:
            assert list(np.asarray(new_c["kv"]["index"][0])) == [1, 0]


def test_launch_serve_takes_the_cut():
    from repro.launch.serve import main

    stats = main(["--arch", "jamba2_mini", "--smoke", "--experts-held",
                  "0:4", "--vocab-slice", "128", "--requests", "2",
                  "--slots", "2", "--max-tokens", "3"])
    assert stats["requests"] == 2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_per_slot_kv_write_matches_one_hot_select(dtype):
    """The per-slot decode writes each row's one position as a one-hot
    select over the whole cache would, bit for bit, on K and V: rows at
    position 0, in the middle and at ``max_seq - 1``; a row with valid
    0 writes and does not advance; an index at ``max_seq`` writes
    nothing."""
    from repro.models.common import apply_attn, init_attn, rmsnorm

    cfg = _cfg(dtype)
    s_max = 32
    idx = jnp.asarray([0, 13, s_max - 1, 7, s_max], jnp.int32)
    valid = jnp.asarray([1, 1, 1, 0, 1], jnp.int32)
    b = idx.shape[0]
    p, _ = init_attn(T.KeyGen(jax.random.key(7)), cfg)
    keys = jax.random.split(jax.random.key(8), 3)
    x = jax.random.normal(keys[0], (b, 1, cfg.d_model), dtype)
    shape = (b, s_max, cfg.kv_heads, cfg.head_dim)
    cache = {"k": jax.random.normal(keys[1], shape, dtype),
             "v": jax.random.normal(keys[2], shape, dtype),
             "index": idx}
    _, got = apply_attn(p, x, cfg, positions=idx[:, None],
                        cache=dict(cache), valid=valid)
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    sel = (jnp.arange(s_max)[None, :] == idx[:, None])[..., None, None]
    for name in ("k", "v"):
        new = jnp.einsum("bsd,dhk->bshk", h, p["w" + name])
        want = jnp.where(sel, new.astype(dtype), cache[name])
        bits = np.uint16 if dtype == jnp.bfloat16 else np.uint32
        assert np.array_equal(np.asarray(got[name]).view(bits),
                              np.asarray(want).view(bits)), name
        assert not np.array_equal(np.asarray(got[name][:3]),
                                  np.asarray(cache[name][:3])), name
        assert np.array_equal(np.asarray(got[name][4]),
                              np.asarray(cache[name][4])), name
    assert list(np.asarray(got["index"])) == [1, 14, s_max, 7, s_max + 1]


@pytest.mark.parametrize("periods", [1, 2])
def test_decode_temporaries_do_not_scale_with_the_cache(periods):
    """The compiled batched decode (16 slots, caches donated) writes
    its K and V in place: doubling ``max_seq`` from 1024 to 2048 grows
    its temporaries by under a tenth of one K cache's growth with one
    period. With two, each period's K and V slice is copied out of the
    stacked caches and back, two K caches' growth; a select over the
    caches and stacking them as the layer scan's outputs grew them by
    six."""
    base = get_smoke_config("jamba2_mini")
    cfg = dataclasses.replace(base, dtype=jnp.float32,
                              layers=periods * base.period)
    slots = 16
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0))[0])
    toks = jax.ShapeDtypeStruct((slots, 1), jnp.int32)
    valid = jax.ShapeDtypeStruct((slots,), jnp.int32)

    def temp(max_seq):
        caches = jax.eval_shape(lambda: T.init_caches(cfg, slots, max_seq))
        step = lm_steps(cfg, max_seq).decode
        compiled = step.lower(params, toks, valid, caches).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    k_growth = slots * 1024 * cfg.kv_heads * cfg.head_dim * 4
    growth = temp(2048) - temp(1024)
    assert growth <= (0.1 if periods == 1 else 3) * k_growth, growth


def _engage_decode_kernel(monkeypatch, block):
    """Route the per-slot decode's attention through the interpreted
    kernel, in blocks of ``block`` positions of the f32 smoke caches."""
    from repro.kernels.decode_attention import ops as decode_ops

    cfg = _cfg()
    monkeypatch.setattr(decode_ops, "engaged", lambda cache: True)
    monkeypatch.setattr(decode_ops, "BLOCK_BYTES",
                        block * cfg.kv_heads * cfg.head_dim * 4)


def _serve(prompts, *, n_slots, max_seq, ticks):
    cfg = _cfg()
    params, _ = T.init_params(cfg, jax.random.key(2))
    eng = ServeEngine(params, cfg, n_slots=n_slots, max_seq=max_seq,
                      prefill_chunk=8)
    reqs = [Request(f"r{i}", p, max_tokens=ticks + 2)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    logits = []
    for _ in range(ticks):
        eng.step()
        logits.append(np.asarray(eng.last_logits))
    return eng, reqs, logits


def test_engine_decodes_the_same_tokens_through_the_kernel(monkeypatch):
    """ServeEngine with the per-slot decode's attention in the kernel
    (blocks of 16, prompts across block edges, one slot idle) gives the
    jnp path's tokens tick by tick, and its logits to float32 rounding;
    the idle slot's logits are finite."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, _cfg().vocab, n) for n in (5, 15, 31)]
    kw = dict(n_slots=4, max_seq=48, ticks=6)
    _, want_reqs, want_logits = _serve(prompts, **kw)
    _engage_decode_kernel(monkeypatch, 16)
    _, got_reqs, got_logits = _serve(prompts, **kw)
    assert [r.generated for r in got_reqs] == \
        [r.generated for r in want_reqs]
    for got, want in zip(got_logits, want_logits):
        assert _err(got[:3], want[:3]) < 1e-5
        assert np.all(np.isfinite(got))


def test_kernel_decode_leaves_idle_slots_as_they_were(monkeypatch):
    """Through the kernel, a row with valid 0 neither advances its KV
    index nor moves its conv or recurrent state, and its logits are
    finite (its attention reads nothing and gives zeros)."""
    _engage_decode_kernel(monkeypatch, 16)
    cfg = _cfg()
    params, _ = T.init_params(cfg, jax.random.key(6))
    caches = T.init_caches(cfg, 2, 40)
    caches = jax.tree.map(lambda a: a + 1 if a.dtype != jnp.int32 else a,
                          caches)
    caches = [dict(c, kv=dict(c["kv"], index=jnp.asarray([[20, 33]],
                                                          jnp.int32)))
              if "kv" in c else c for c in caches]
    tok = jnp.asarray([[3], [4]], jnp.int32)
    logits, new = T.decode_step(params, tok, caches, cfg,
                                valid=jnp.asarray([1, 0], jnp.int32))
    assert np.all(np.isfinite(np.asarray(logits)))
    for old_c, new_c in zip(caches, new):
        if "ssm" in old_c:
            for k in ("conv", "h"):
                assert np.array_equal(old_c["ssm"][k][:, 1],
                                      new_c["ssm"][k][:, 1])
                assert not np.array_equal(old_c["ssm"][k][:, 0],
                                          new_c["ssm"][k][:, 0])
        else:
            assert list(np.asarray(new_c["kv"]["index"][0])) == [21, 33]


@pytest.mark.parametrize("engaged", [False, True], ids=["jnp", "kernel"])
def test_kv_read_bytes_counts_what_the_attention_reads(monkeypatch,
                                                       engaged):
    """``cache.kv_read_bytes``: through the kernel, each slot's visible
    length rounded up to the block (an idle slot fetches one block);
    otherwise all ``max_seq`` positions of every slot."""
    if engaged:
        _engage_decode_kernel(monkeypatch, 16)
    cfg = _cfg()
    eng, _, _ = _serve([np.arange(5), np.arange(20)], n_slots=3,
                       max_seq=48, ticks=2)
    per_pos = 2 * cfg.kv_heads * cfg.head_dim * 4
    # visible lengths: 6 and 21 in the first tick, 7 and 22 in the next
    positions = (16 + 32 + 16) * 2 if engaged else 3 * 48 * 2
    scope = eng._mscope
    assert scope.counter("cache.kv_read_bytes").value == positions * per_pos
    assert scope.counter("cache.kv_bytes").value == \
        (6 + 21 + 7 + 22) * per_pos
