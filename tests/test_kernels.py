"""Pallas kernel parity: interpret-mode kernels vs pure-jnp oracles.

Hypothesis shape/dtype sweeps run when hypothesis is installed; the
deterministic parity tests below always run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.matmul.ops import matmul
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.mamba_scan.ops import mamba_scan
from repro.kernels.mamba_scan.ref import mamba_scan_ref

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

def _check_matmul(m, k, n, dtype):
    rng = np.random.default_rng(m * 1000 + k * 10 + n)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    y = jnp.asarray(rng.normal(size=(k, n)), dtype)
    got = matmul(x, y, force_pallas=True, interpret=True,
                 bm=32, bn=32, bk=64)
    ref = matmul_ref(x, y)
    tol = 2e-2 if dtype == "bfloat16" else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_matmul_smoke():
    _check_matmul(64, 32, 16, "float32")
    _check_matmul(100, 300, 64, "float32")


if HAVE_HYPOTHESIS:
    @given(
        m=st.sampled_from([16, 64, 100, 128]),
        k=st.sampled_from([32, 128, 300]),
        n=st.sampled_from([16, 64, 200]),
        dtype=st.sampled_from(["float32", "bfloat16"]),
    )
    @settings(max_examples=12, deadline=None)
    def test_matmul_sweep(m, k, n, dtype):
        _check_matmul(m, k, n, dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _check_flash_attention(sq, h, kvh, d, window, softcap):
    if h % kvh:
        kvh = 1
    rng = np.random.default_rng(sq + h * 7 + d)
    q = jnp.asarray(rng.normal(size=(1, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, sq, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, sq, kvh, d)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=window,
                          softcap=softcap, force_pallas=True,
                          interpret=True, bq=32, bk=32)
    ref = attention_ref(q, k, v, causal=True, window=window,
                        softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_smoke():
    _check_flash_attention(64, 2, 1, 32, 0, 0.0)
    _check_flash_attention(64, 4, 2, 32, 32, 30.0)


if HAVE_HYPOTHESIS:
    @given(
        sq=st.sampled_from([64, 128]),
        h=st.sampled_from([2, 4]),
        kvh=st.sampled_from([1, 2]),
        d=st.sampled_from([32, 64]),
        window=st.sampled_from([0, 32]),
        softcap=st.sampled_from([0.0, 30.0]),
    )
    @settings(max_examples=10, deadline=None)
    def test_flash_attention_sweep(sq, h, kvh, d, window, softcap):
        _check_flash_attention(sq, h, kvh, d, window, softcap)


def test_flash_attention_matches_model_chunked_path():
    """The model's chunked_attention and the Pallas kernel agree."""
    from repro.models.common import chunked_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 128, 2, 32)), jnp.float32)
    a = chunked_attention(q, k, v, causal=True, chunk=32)
    b = flash_attention(q, k, v, causal=True, force_pallas=True,
                        interpret=True, bq=32, bk=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5,
                               rtol=3e-5)


# ---------------------------------------------------------------------------
# mamba scan
# ---------------------------------------------------------------------------

def _check_mamba_scan(l, inner, n, chunk):
    rng = np.random.default_rng(l + inner + n)
    B = 2
    x = jnp.asarray(rng.normal(size=(B, l, inner)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(B, l, inner))) * 0.1,
                     jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, l, n)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, l, n)), jnp.float32)
    a = jnp.asarray(np.log(np.abs(rng.normal(size=(inner, n))) + 0.5),
                    jnp.float32)
    d = jnp.asarray(rng.normal(size=(inner,)), jnp.float32)
    got = mamba_scan(x, dt, Bm, Cm, a, d, chunk=chunk,
                     force_pallas=True, interpret=True)
    ref = mamba_scan_ref(x, dt, Bm, Cm, a, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_mamba_scan_smoke():
    _check_mamba_scan(32, 8, 4, 8)


if HAVE_HYPOTHESIS:
    @given(
        l=st.sampled_from([32, 64]),
        inner=st.sampled_from([8, 16]),
        n=st.sampled_from([4, 8]),
        chunk=st.sampled_from([8, 16]),
    )
    @settings(max_examples=8, deadline=None)
    def test_mamba_scan_sweep(l, inner, n, chunk):
        _check_mamba_scan(l, inner, n, chunk)


def test_mamba_scan_chunking_invariance():
    """Chunk size must not change results (state carried across chunks)."""
    rng = np.random.default_rng(9)
    B, L, I, N = 1, 48, 8, 4
    x = jnp.asarray(rng.normal(size=(B, L, I)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(B, L, I))) * 0.1,
                     jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, L, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, L, N)), jnp.float32)
    a = jnp.asarray(np.log(np.abs(rng.normal(size=(I, N))) + 0.5),
                    jnp.float32)
    d = jnp.asarray(rng.normal(size=(I,)), jnp.float32)
    o1 = mamba_scan(x, dt, Bm, Cm, a, d, chunk=8, force_pallas=True,
                    interpret=True)
    o2 = mamba_scan(x, dt, Bm, Cm, a, d, chunk=16, force_pallas=True,
                    interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

_DECODE_BLOCK = 16


@pytest.mark.parametrize("seq", [64, 72], ids=["S64", "S72"])
@pytest.mark.parametrize("softcap", [0.0, 50.0], ids=["cap0", "cap50"])
@pytest.mark.parametrize("window", [0, 24, 5],
                         ids=["nowin", "win_gt_block", "win_lt_block"])
@pytest.mark.parametrize("groups", [1, 4], ids=["g1", "g4"])
def test_decode_attention_matches_model_paths(monkeypatch, groups, window,
                                              softcap, seq):
    """The interpreted kernel, through its wrapper, against the model's
    chunked and plain attention (and the kernel's oracle) for one query
    a slot at visible lengths 1, block - 1, block, block + 1 and S, in
    blocks of 16 positions; S = 72 leaves a part block at the end. A
    row with valid 0 gives zeros."""
    from repro.kernels.decode_attention import ops as decode_ops
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.models.common import chunked_attention, plain_attention

    kvh, d = 2, 32
    monkeypatch.setattr(decode_ops, "BLOCK_BYTES",
                        _DECODE_BLOCK * kvh * d * 4)
    blk = _DECODE_BLOCK
    lengths = [1, blk - 1, blk, blk + 1, seq, 30]
    valid = jnp.asarray([1, 1, 1, 1, 1, 0], jnp.int32)
    idx = jnp.asarray(lengths, jnp.int32) - 1
    b, h = len(lengths), kvh * groups
    rng = np.random.default_rng(groups * 100 + window + seq)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, seq, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, seq, kvh, d)), jnp.float32)
    assert decode_ops.block_size(k) == blk

    got = np.asarray(decode_ops.decode_attention(
        q, k, v, idx, valid, window=window, softcap=softcap))
    kw = dict(causal=True, window=window, cap=softcap, q_offset=idx)
    live = np.asarray(valid) == 1
    for want in (chunked_attention(q, k, v, chunk=blk, **kw),
                 plain_attention(q, k, v, **kw)):
        np.testing.assert_allclose(got[live], np.asarray(want)[live],
                                   atol=1e-5, rtol=1e-5)
    n, first = decode_ops.visible_range(idx, valid, seq, window)
    ref = decode_attention_ref(q[:, 0], k, v, n, first, softcap=softcap)
    np.testing.assert_allclose(got[:, 0], np.asarray(ref), atol=1e-5,
                               rtol=1e-5)
    assert np.all(np.isfinite(got)) and not np.any(got[~live])


def test_decode_kv_block_index_clamps_to_visible_blocks():
    """The K/V index map as a plain function: steps past a slot's last
    visible block map to that block, steps before its first (a window)
    to the first, and an empty slot to block 0."""
    from repro.kernels.decode_attention.decode_attention import \
        kv_block_index

    def blocks(first, n, steps=6, block=16):
        return [int(kv_block_index(j, first, n, block))
                for j in range(steps)]

    assert blocks(0, 40) == [0, 1, 2, 2, 2, 2]
    assert blocks(0, 32) == [0, 1, 1, 1, 1, 1]
    assert blocks(0, 1) == [0] * 6
    assert blocks(20, 40) == [1, 1, 2, 2, 2, 2]
    assert blocks(35, 70) == [2, 2, 2, 3, 4, 4]
    assert blocks(0, 0) == [0] * 6
