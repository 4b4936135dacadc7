"""Profiler subsystem: trace → hint synthesis → compile → dispatch loop,
persistent variant cache, and hot-call-site specialization."""

import numpy as np
import pytest

from repro.core.compiler import compile_kernel, optimize
from repro.profiler import (Specializer, Tracer, VariantCache, cache_key,
                            source_hash, synthesize_hint_tiers,
                            synthesize_hints)
from repro.profiler.hints import ShapeGuard, pow2_bucket, type_signature


# Module-level kernels: the front-end reads their source via inspect.
def gemm_unhinted(C, A, B, alpha, beta, M, N, K):
    for i in range(0, M):
        for j in range(0, N):
            C[i, j] = C[i, j] * beta
            for k in range(0, K):
                C[i, j] = C[i, j] + alpha * A[i, k] * B[k, j]


def atax_unhinted(A, x, y, tmp, M, N):
    for i in range(0, M):
        tmp[i] = 0.0
        for j in range(0, N):
            tmp[i] = tmp[i] + A[i, j] * x[j]
    for i in range(0, M):
        for j in range(0, N):
            y[j] = y[j] + A[i, j] * tmp[i]


def _gemm_args(n, seed=0):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(n, n))
    B = rng.normal(size=(n, n))
    C = rng.normal(size=(n, n))
    return C, A, B


def _gemm_ref(C, A, B, alpha, beta):
    return C * beta + alpha * (A @ B)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_records_signatures_and_latency():
    tr = Tracer()
    traced = tr.wrap(gemm_unhinted)
    C, A, B = _gemm_args(8)
    for _ in range(3):
        traced(C.copy(), A, B, 1.0, 0.5, 8, 8, 8)
    traced(np.zeros((4, 4)), np.ones((4, 4)), np.ones((4, 4)),
           1.0, 0.5, 4, 4, 4)
    trace = tr.trace_of(traced)
    assert trace.calls == 4
    assert len(trace.records) == 2          # two distinct signatures
    dom = trace.dominant
    assert dom.calls == 3                   # hottest first
    assert dom.total_s > 0
    by_name = {o.name: o for o in dom.args}
    assert by_name["A"].dtype == "float64" and by_name["A"].rank == 2
    assert by_name["A"].shape == (8, 8)
    assert by_name["M"].kind == "scalar"
    assert "gemm_unhinted" in tr.report()


# ---------------------------------------------------------------------------
# hint synthesis
# ---------------------------------------------------------------------------

def test_hint_synthesis_produces_parser_consumable_hints():
    tr = Tracer()
    traced = tr.wrap(gemm_unhinted)
    C, A, B = _gemm_args(10)
    traced(C.copy(), A, B, 1.5, 0.5, 10, 10, 10)
    hints = synthesize_hints(tr.trace_of(traced))
    assert hints["A"] == "ndarray[f64,2]"
    assert hints["alpha"] == "float"
    assert hints["M"] == "int"
    # the strings must round-trip through the front-end type parser
    from repro.core.types import parse_annotation
    ti = parse_annotation(hints["A"])
    assert ti.kind == "array" and ti.dtype == "float64" and ti.rank == 2


def test_hint_tiers_are_legality_ordered():
    tr = Tracer()
    traced = tr.wrap(gemm_unhinted)
    C, A, B = _gemm_args(12)
    traced(C.copy(), A, B, 1.0, 1.0, 12, 12, 12)
    tiers = synthesize_hint_tiers(tr.trace_of(traced))
    assert [t.name for t in tiers] == ["exact", "bucket", "rank"]
    shapes = {"A": (12, 12), "B": (12, 12), "C": (12, 12)}
    assert tiers[0].admits(shapes)          # exact shapes admitted
    assert not tiers[0].admits({**shapes, "A": (13, 12)})
    assert tiers[1].admits({**shapes, "A": (13, 12)})   # (8,16] bucket
    assert not tiers[1].admits({**shapes, "A": (17, 12)})
    assert tiers[2].admits({**shapes, "A": (1000, 3)})  # rank-only


def test_pow2_bucket_and_guards():
    assert pow2_bucket(1) == (0, 1)
    assert pow2_bucket(4) == (2, 4)
    assert pow2_bucket(100) == (64, 128)
    g = ShapeGuard.exact((5, 7))
    assert g.admits((5, 7)) and not g.admits((5, 8))
    b = ShapeGuard.bucketed((100,))
    assert b.admits((65,)) and b.admits((128,)) and not b.admits((64,))


def test_mixed_rank_widens_to_rankless_ndarray():
    tr = Tracer()

    def poly(x):
        return x

    traced = tr.wrap(poly)
    traced(np.zeros((3, 3)))
    traced(np.zeros(3))
    hints = synthesize_hints(tr.trace_of(traced))
    assert hints["x"] == "ndarray"


# ---------------------------------------------------------------------------
# end-to-end: trace → hints → compile → dispatch
# ---------------------------------------------------------------------------

def test_profile_then_compile_matches_original():
    """Acceptance: no hand-written hints anywhere, results allclose."""
    ck = optimize(gemm_unhinted, profile=True, warmup=3)
    n = 10
    C0, A, B = _gemm_args(n, seed=3)
    ref = _gemm_ref(C0, A, B, 1.5, 0.5)
    for _ in range(5):                      # 3 traced + 2 dispatched
        C = C0.copy()
        ck(C, A, B, 1.5, 0.5, n, n, n)
        np.testing.assert_allclose(C, ref, atol=1e-8)
    assert ck.compiled is not None
    assert ck.stats()["dispatch"]["calls"] >= 2
    # legality fallback survives: a wrong-rank call still succeeds via
    # the original function
    assert ck.compiled.select(
        ck.compiled._bind([np.zeros(3), A, B, 1.5, 0.5, n, n, n], {})
    )[0].name == "original"


def test_from_trace_entry_point():
    tr = Tracer()
    traced = tr.wrap(atax_unhinted)
    M, N = 12, 9
    rng = np.random.default_rng(1)
    A = rng.normal(size=(M, N))
    x = rng.normal(size=N)
    for _ in range(2):
        traced(A, x, np.zeros(N), np.zeros(M), M, N)
    ck = optimize.from_trace(traced)
    y = np.zeros(N)
    tmp = np.zeros(M)
    ck(A, x, y, tmp, M, N)
    np.testing.assert_allclose(y, A.T @ (A @ x), atol=1e-8)
    assert ck.last_dispatch.legality_ok


# ---------------------------------------------------------------------------
# persistent variant cache
# ---------------------------------------------------------------------------

def test_cache_survives_process_restart(tmp_path):
    """New cache object over the same dir (simulated restart) must hit
    and skip codegen entirely — verified by telemetry counters."""
    d = str(tmp_path / "vcache")
    hints = {"C": "ndarray[f64,2]", "A": "ndarray[f64,2]",
             "B": "ndarray[f64,2]", "alpha": "float", "beta": "float",
             "M": "int", "N": "int", "K": "int"}

    cache1 = VariantCache(d)
    ck1 = compile_kernel(gemm_unhinted, hints=hints, cache=cache1)
    assert cache1.stats.misses == 1 and cache1.stats.puts == 1
    assert cache1.stats.codegen_skipped == 0
    assert not ck1.from_cache

    cache2 = VariantCache(d)                # fresh object, same dir
    assert cache2.stats.hits == 0
    ck2 = compile_kernel(gemm_unhinted, hints=hints, cache=cache2)
    assert cache2.stats.hits == 1
    assert cache2.stats.codegen_skipped == 1    # parse→codegen skipped
    assert ck2.from_cache

    # the warm kernel computes the same thing
    n = 8
    C0, A, B = _gemm_args(n, seed=7)
    ref = _gemm_ref(C0, A, B, 2.0, 0.25)
    C = C0.copy()
    ck2(C, A, B, 2.0, 0.25, n, n, n)
    np.testing.assert_allclose(C, ref, atol=1e-8)
    # both kernels generated identical variant source
    assert ck1.source("np") == ck2.source("np")


def test_cache_key_discriminates(tmp_path):
    d = str(tmp_path / "vcache")
    cache = VariantCache(d)
    hints64 = {"C": "ndarray[f64,2]", "A": "ndarray[f64,2]",
               "B": "ndarray[f64,2]", "alpha": "float", "beta": "float",
               "M": "int", "N": "int", "K": "int"}
    hints32 = dict(hints64, A="ndarray[f32,2]")
    compile_kernel(gemm_unhinted, hints=hints64, cache=cache)
    compile_kernel(gemm_unhinted, hints=hints32, cache=cache)
    assert cache.stats.misses == 2 and cache.stats.puts == 2
    assert len(cache.entries()) == 2
    # distinct backends key separately too
    assert cache_key("s", "t", "np") != cache_key("s", "t", "np+jnp")
    assert source_hash(gemm_unhinted) != source_hash(atax_unhinted)


def test_cache_key_includes_codegen_options(tmp_path):
    """distribute changes the schedule, so it must key separately —
    a distribute=True request must never get a distribute=False hit."""
    d = str(tmp_path / "vcache")
    hints = {"C": "ndarray[f64,2]", "A": "ndarray[f64,2]",
             "B": "ndarray[f64,2]", "alpha": "float", "beta": "float",
             "M": "int", "N": "int", "K": "int"}
    compile_kernel(gemm_unhinted, hints=hints, distribute=False,
                   cache=VariantCache(d))
    ck = compile_kernel(gemm_unhinted, hints=hints, distribute=True,
                        cache=VariantCache(d))
    assert not ck.from_cache                # distinct key → cold compile
    ck2 = compile_kernel(gemm_unhinted, hints=hints, distribute=True,
                         cache=VariantCache(d))
    assert ck2.from_cache                   # same options → warm


def test_tracer_context_restores_recording_state():
    tr = Tracer()
    traced = tr.wrap(gemm_unhinted)
    C, A, B = _gemm_args(4)
    with tr:
        traced(C.copy(), A, B, 1.0, 1.0, 4, 4, 4)
    traced(C.copy(), A, B, 1.0, 1.0, 4, 4, 4)   # still recording after
    assert tr.trace_of(traced).calls == 2
    tr.pause()
    traced(C.copy(), A, B, 1.0, 1.0, 4, 4, 4)   # paused: not recorded
    assert tr.trace_of(traced).calls == 2
    with tr:                                     # context forces on...
        traced(C.copy(), A, B, 1.0, 1.0, 4, 4, 4)
    assert tr.trace_of(traced).calls == 3
    traced(C.copy(), A, B, 1.0, 1.0, 4, 4, 4)   # ...and restores pause
    assert tr.trace_of(traced).calls == 3


def test_tracer_same_name_functions_do_not_share_traces():
    tr = Tracer()

    def make(mult):
        def f(x):
            return x * mult
        return f

    t1, t2 = tr.wrap(make(2)), tr.wrap(make(3))
    t1(np.zeros((2, 2)))
    t1(np.zeros((2, 2)))
    t2(np.zeros(5))
    assert tr.trace_of(t1) is not tr.trace_of(t2)
    assert tr.trace_of(t1).calls == 2
    assert tr.trace_of(t2).calls == 1


def test_cache_corrupt_entry_degrades_to_cold_compile(tmp_path):
    d = str(tmp_path / "vcache")
    hints = {"C": "ndarray[f64,2]", "A": "ndarray[f64,2]",
             "B": "ndarray[f64,2]", "alpha": "float", "beta": "float",
             "M": "int", "N": "int", "K": "int"}
    cache = VariantCache(d)
    compile_kernel(gemm_unhinted, hints=hints, cache=cache)
    key = cache.entries()[0]
    with open(cache._path(key), "wb") as f:
        f.write(b"not a pickle")
    cache2 = VariantCache(d)
    ck = compile_kernel(gemm_unhinted, hints=hints, cache=cache2)
    assert not ck.from_cache
    assert cache2.stats.errors == 1
    assert cache2.stats.puts == 1           # re-cached after recompile


def test_cache_index_dump(tmp_path):
    import json
    d = str(tmp_path / "vcache")
    cache = VariantCache(d)
    hints = {"C": "ndarray[f64,2]", "A": "ndarray[f64,2]",
             "B": "ndarray[f64,2]", "alpha": "float", "beta": "float",
             "M": "int", "N": "int", "K": "int"}
    compile_kernel(gemm_unhinted, hints=hints, cache=cache)
    path = cache.dump_index()
    idx = json.load(open(path))
    assert idx[0]["fn"] == "gemm_unhinted"
    assert "f64" in idx[0]["type_sig"] or "float64" in idx[0]["type_sig"]


# ---------------------------------------------------------------------------
# specializer
# ---------------------------------------------------------------------------

def test_specializer_promotes_hot_signature_and_stays_correct():
    hints = {"C": "ndarray[f64,2]", "A": "ndarray[f64,2]",
             "B": "ndarray[f64,2]", "alpha": "float", "beta": "float",
             "M": "int", "N": "int", "K": "int"}
    ck = compile_kernel(gemm_unhinted, hints=hints)
    sp = Specializer(hot_threshold=4)
    sp.register(ck)
    n = 8
    C0, A, B = _gemm_args(n, seed=11)
    ref = _gemm_ref(C0, A, B, 1.0, 1.0)
    for _ in range(5):
        C = C0.copy()
        ck(C, A, B, 1.0, 1.0, n, n, n)
    promoted = sp.scan_once()
    assert len(promoted) == 1
    assert promoted[0].variant_name == "np"
    # pinned fast path still produces identical results
    C = C0.copy()
    ck(C, A, B, 1.0, 1.0, n, n, n)
    np.testing.assert_allclose(C, ref, atol=1e-8)
    assert ck.spec_hits == 1
    # mild shape drift inside the same pow2 bucket (6 and 8 are both in
    # (4, 8]) keeps the pinned fast path via the bucket tier
    m = 6
    C0b, Ab, Bb = _gemm_args(m, seed=12)
    Cb = C0b.copy()
    ck(Cb, Ab, Bb, 1.0, 1.0, m, m, m)
    np.testing.assert_allclose(Cb, _gemm_ref(C0b, Ab, Bb, 1.0, 1.0),
                               atol=1e-8)
    assert ck.bucket_hits == 1
    assert ck.spec_hits == 2
    # a shape *outside* the bucket bypasses both tiers and walks the tree
    m = 16
    C0c, Ac, Bc = _gemm_args(m, seed=13)
    Cc = C0c.copy()
    ck(Cc, Ac, Bc, 1.0, 1.0, m, m, m)
    np.testing.assert_allclose(Cc, _gemm_ref(C0c, Ac, Bc, 1.0, 1.0),
                               atol=1e-8)
    assert ck.bucket_hits == 1              # unchanged
    assert ck.spec_hits == 2                # unchanged
    assert sp.telemetry()["promotions"] == 1


def test_specializer_background_thread_lifecycle():
    sp = Specializer(hot_threshold=1, interval_s=0.01)
    hints = {"C": "ndarray[f64,2]", "A": "ndarray[f64,2]",
             "B": "ndarray[f64,2]", "alpha": "float", "beta": "float",
             "M": "int", "N": "int", "K": "int"}
    ck = compile_kernel(gemm_unhinted, hints=hints)
    sp.register(ck)
    n = 6
    C0, A, B = _gemm_args(n, seed=13)
    with sp:
        assert sp.telemetry()["running"]
        import time
        deadline = time.time() + 2.0
        while not ck.specializations and time.time() < deadline:
            C = C0.copy()
            ck(C, A, B, 1.0, 1.0, n, n, n)
            time.sleep(0.01)
    assert not sp.telemetry()["running"]
    assert len(ck.specializations) >= 1


def test_original_fallback_preserved_after_specialization():
    """Wrong dtype after promotion: full tree still catches it."""
    hints = {"C": "ndarray[f64,2]", "A": "ndarray[f64,2]",
             "B": "ndarray[f64,2]", "alpha": "float", "beta": "float",
             "M": "int", "N": "int", "K": "int"}
    ck = compile_kernel(gemm_unhinted, hints=hints)
    sp = Specializer(hot_threshold=2)
    sp.register(ck)
    n = 6
    C0, A, B = _gemm_args(n, seed=14)
    for _ in range(3):
        C = C0.copy()
        ck(C, A, B, 1.0, 1.0, n, n, n)
    sp.scan_once()
    bad = A.astype(np.int64)                # dtype violates legality
    C = C0.copy()
    ck(C, bad, B, 1.0, 1.0, n, n, n)
    assert ck.last_dispatch.variant == "original"
    assert not ck.last_dispatch.legality_ok


# ---------------------------------------------------------------------------
# serving telemetry + type_signature helper
# ---------------------------------------------------------------------------

def test_type_signature_helper():
    sig = type_signature({"A": "ndarray[f64,2]", "n": "int"}, ["A", "n"])
    assert sig == "A:array[float64,2];n:scalar[int64,0]"
    # alias spellings canonicalize to the same key
    assert sig == type_signature({"A": "ndarray[float64,2]", "n": "i64"},
                                 ["A", "n"])


def test_engine_telemetry_exposes_dispatch_and_cache(tmp_path):
    """serve.engine folds kernel dispatch + variant cache counters into
    one telemetry endpoint (no model needed for this surface)."""
    from repro.serve.engine import ServeEngine

    hints = {"C": "ndarray[f64,2]", "A": "ndarray[f64,2]",
             "B": "ndarray[f64,2]", "alpha": "float", "beta": "float",
             "M": "int", "N": "int", "K": "int"}
    cache = VariantCache(str(tmp_path / "vc"))
    ck = compile_kernel(gemm_unhinted, hints=hints, cache=cache)
    sp = Specializer(hot_threshold=1)
    sp.register(ck, name="gemm")
    n = 6
    C0, A, B = _gemm_args(n, seed=15)
    C = C0.copy()
    ck(C, A, B, 1.0, 1.0, n, n, n)

    eng = ServeEngine.__new__(ServeEngine)  # telemetry-only surface
    eng.queue, eng.active, eng.finished = [], {}, []
    eng.ticks = eng.prefills = eng.tokens_generated = 0
    from repro.serve.kvcache import SlotMap
    eng.slots = SlotMap(2)
    eng.kernel_registry = sp
    eng.variant_cache = cache
    t = eng.telemetry()
    assert t["kernels"]["kernels"]["gemm"]["calls"] == 1
    assert t["variant_cache"]["puts"] == 1
    assert t["ticks"] == 0


# ---------------------------------------------------------------------------
# specializer demotion
# ---------------------------------------------------------------------------

def _hot_compiled_gemm(n=8, hot=4, **spec_kw):
    hints = {"C": "ndarray[f64,2]", "A": "ndarray[f64,2]",
             "B": "ndarray[f64,2]", "alpha": "float", "beta": "float",
             "M": "int", "N": "int", "K": "int"}
    ck = compile_kernel(gemm_unhinted, hints=hints)
    sp = Specializer(hot_threshold=hot, **spec_kw)
    sp.register(ck)
    C0, A, B = _gemm_args(n, seed=21)
    for _ in range(hot + 1):
        C = C0.copy()
        ck(C, A, B, 1.0, 1.0, n, n, n)
    assert len(sp.scan_once()) == 1
    return ck, sp, (C0, A, B, n)


def test_specializer_demotes_cold_signature():
    ck, sp, (C0, A, B, n) = _hot_compiled_gemm(demote_cold_scans=2,
                                               cold_after_s=0.0)
    sig = next(iter(ck.specializations))
    # one hit keeps it warm through the first scans
    C = C0.copy()
    ck(C, A, B, 1.0, 1.0, n, n, n)
    sp.scan_once()
    assert sig in ck.specializations
    # no further hits: cold after `demote_cold_scans` idle scans
    sp.scan_once()
    sp.scan_once()
    assert sig not in ck.specializations
    assert sp.telemetry()["demoted"] == 1
    assert sp.demotions[0][2] == "cold"
    # the hot window restarted — the signature can re-earn its pin
    assert ck.shape_counts[sig] == 0
    ref = _gemm_ref(C0, A, B, 1.0, 1.0)
    C = C0.copy()
    ck(C, A, B, 1.0, 1.0, n, n, n)   # falls back to the full tree
    np.testing.assert_allclose(C, ref, atol=1e-8)


def test_specializer_demotes_latency_regression():
    ck, sp, (C0, A, B, n) = _hot_compiled_gemm(
        demote_cold_scans=1000, min_hits_for_regress=1,
        regress_factor=1.5)
    sig = next(iter(ck.specializations))
    spec = ck.specializations[sig]
    # keep the pin warm but make its measured latency look regressed
    C = C0.copy()
    ck(C, A, B, 1.0, 1.0, n, n, n)
    ck.tree_latency[sig] = 1e-6
    spec.latency_ema = 1e-2
    sp.scan_once()
    assert sig not in ck.specializations
    assert sp.demotions[0][2] == "latency_regression"


def test_demotion_frees_slot_for_new_promotion():
    ck, sp, (C0, A, B, n) = _hot_compiled_gemm(
        demote_cold_scans=1, cold_after_s=0.0,
        max_specializations_per_kernel=1)
    assert len(ck.specializations) == 1
    # drive a different (hot) signature while the pinned one idles
    m = 16
    C0b, Ab, Bb = _gemm_args(m, seed=22)
    for _ in range(6):
        Cb = C0b.copy()
        ck(Cb, Ab, Bb, 1.0, 1.0, m, m, m)
    # one scan: the demote sweep runs first, freeing the only slot, and
    # the promotion pass immediately pins the new hot signature into it
    promoted = sp.scan_once()
    assert len(promoted) == 1
    assert len(ck.specializations) == 1
    assert next(iter(ck.specializations)) == promoted[0].sig
    assert sp.telemetry()["demoted"] >= 1
