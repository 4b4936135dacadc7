"""Serving engine + distributed pfor integration tests."""

import numpy as np
import pytest

from repro.core.compiler import compile_kernel
from repro.core.pfor import PforConfig
from repro.distrib import ClusterRuntime


@pytest.mark.slow
def test_engine_continuous_batching_matches_sequential():
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    from repro.serve.engine import Request, ServeEngine

    cfg = get_smoke_config("stablelm_3b")
    params, _ = T.init_params(cfg, jax.random.key(5))
    eng = ServeEngine(params, cfg, n_slots=2, max_seq=48)
    prompts = [np.arange(4) % cfg.vocab, np.arange(7) % cfg.vocab,
               np.arange(5) % cfg.vocab]
    for i, p in enumerate(prompts):
        eng.add_request(Request(f"r{i}", p, max_tokens=5))
    done = eng.run_until_done()
    assert len(done) == 3
    by_id = {r.request_id: r for r in done}

    # sequential reference: prefill + greedy decode per request
    for i, p in enumerate(prompts):
        caches, logits = T.prefill(
            params, {"tokens": jnp.asarray(p, jnp.int32)[None]}, cfg,
            max_seq=48)
        toks = [int(jnp.argmax(logits[0]))]
        for _ in range(4):
            l2, caches = T.decode_step(
                params, jnp.asarray([[toks[-1]]], jnp.int32), caches, cfg)
            toks.append(int(jnp.argmax(l2[0])))
        assert by_id[f"r{i}"].generated == toks, f"request {i}"


def test_engine_slot_reuse():
    import jax

    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    from repro.serve.engine import Request, ServeEngine

    cfg = get_smoke_config("stablelm_3b")
    params, _ = T.init_params(cfg, jax.random.key(6))
    eng = ServeEngine(params, cfg, n_slots=1, max_seq=32)
    for i in range(3):
        eng.add_request(Request(f"r{i}", np.arange(3 + i) % cfg.vocab,
                                max_tokens=3))
    done = eng.run_until_done()
    assert len(done) == 3
    assert eng.slots.utilization() == 0.0


@pytest.fixture(scope="module")
def cluster():
    rt = ClusterRuntime(workers=2)
    yield rt
    rt.shutdown()


def _dispatched(rt):
    st = rt.stats()
    return st["pfor_runs"], st["chunks_dispatched"]


def test_fully_affine_loop_absorbed_not_distributed(cluster):
    """Intra-node maximization wins for fully analyzable loops: the loop
    is absorbed into one vectorized op, no chunks shipped (paper §4.2
    'maximizing the iteration domain mapped to a single library call')."""
    def saxpy(out: "ndarray[f64,2]", A: "ndarray[f64,2]",
              x: "ndarray[f64,1]", N: int):
        for i in range(0, N):
            out[i, :] = A[i, :] * x[i]

    rng = np.random.default_rng(0)
    N, M = 64, 16
    A = rng.normal(size=(N, M))
    x = rng.normal(size=N)
    ck = compile_kernel(saxpy, runtime=cluster)
    before = _dispatched(cluster)
    out = np.zeros((N, M))
    ck.call_variant("np", out, A, x, N)
    np.testing.assert_allclose(out, A * x[:, None])
    assert not ck.sched.has_pfor          # absorbed
    assert _dispatched(cluster) == before


def test_pfor_distributed_matches_sequential(cluster):
    """A loop with a materialization point (fft) stays explicit, is
    detected parallel, and distributes over the cluster's workers."""
    def rowfft(out: "ndarray[c128,2]", A: "ndarray[c128,2]", N: int,
               F: int):
        for i in range(0, N):
            row = np.fft.fft(A[i, :], F)
            out[i, 0:F] = row * 2.0

    rng = np.random.default_rng(0)
    N, M, F = 32, 16, 16
    A = rng.normal(size=(N, M)) + 1j * rng.normal(size=(N, M))
    ref = np.fft.fft(A, F, axis=1) * 2.0

    ck = compile_kernel(rowfft, runtime=cluster, tile=4)
    ck.pfor_config.distribute_threshold = 0  # force the cluster tier
    runs0, chunks0 = _dispatched(cluster)
    out = np.zeros((N, F), complex)
    ck.call_variant("np", out, A, N, F)
    np.testing.assert_allclose(out, ref)
    assert ck.sched.has_pfor
    runs1, chunks1 = _dispatched(cluster)
    assert runs1 == runs0 + 1
    assert chunks1 - chunks0 >= 2  # actually distributed


def test_pfor_sequential_below_threshold(cluster):
    def scale(out: "ndarray[f64,2]", A: "ndarray[f64,2]", N: int):
        for i in range(0, N):
            out[i, :] = A[i, :] * 2.0

    rng = np.random.default_rng(1)
    A = rng.normal(size=(8, 4))
    ck = compile_kernel(scale, runtime=cluster)
    before = _dispatched(cluster)
    # a tiny kernel stays on the caller: nothing is shipped
    out = np.zeros((8, 4))
    ck.call_variant("np", out, A, 8)
    np.testing.assert_allclose(out, A * 2.0)
    assert _dispatched(cluster) == before


def _recurrence(A: "ndarray[f64,2]", s: "ndarray[f64,1]",
                out: "ndarray[f64,1]", N: int, M: int, iters: int):
    # the inner recurrence on a privatized vector keeps the row loop a
    # real pfor (a pure elementwise kernel would absorb)
    for i in range(0, N):
        w = 0.1 * s[0:M]
        for it in range(0, iters):
            w = w + 0.1 * (s[0:M] - A[i, 0:M] * w[0:M])
        out[i] = np.dot(w[0:M], A[i, 0:M])


def _recurrence_data(n=16, m=8):
    rng = np.random.default_rng(2)
    A = rng.normal(size=(n, m))
    s = rng.normal(size=m)
    ref = np.zeros(n)
    _recurrence(A, s, ref, n, m, 5)
    return A, s, ref


def test_pfor_below_profitability_runs_on_head(cluster):
    """A real pfor whose work is too small to ship runs on the head:
    the device-profile cost model declines, no chunk leaves."""
    A, s, ref = _recurrence_data()
    ck = compile_kernel(_recurrence, runtime=cluster)
    assert ck.sched.has_pfor
    before = _dispatched(cluster)
    out = np.zeros(len(ref))
    ck.call_variant("np", A, s, out, len(ref), A.shape[1], 5)
    np.testing.assert_allclose(out, ref, atol=1e-12)
    assert _dispatched(cluster) == before


class _RecordingRT:
    """The pfor runtime protocol, recording every call and running the
    body in this process."""

    def __init__(self, profitable=True):
        self.profitable = profitable
        self.decisions = []
        self.shards = []

    def distribute_profitable(self, flops, bcast_bytes, n_chunks, *,
                              sliced_bytes):
        self.decisions.append((flops, bcast_bytes, n_chunks,
                               sliced_bytes))
        return self.profitable

    def pfor_shards(self, body, lo, hi, tile, *, written, sliceable,
                    est_flops):
        self.shards.append({"body": body, "lo": lo, "hi": hi,
                            "tile": tile, "written": written,
                            "sliceable": sliceable,
                            "est_flops": est_flops})
        body(lo, hi)


def test_pfor_run_passes_protocol_keywords():
    A, s, ref = _recurrence_data()
    rt = _RecordingRT()
    ck = compile_kernel(_recurrence, runtime=rt, tile=4)
    ck.pfor_config.distribute_threshold = 0
    out = np.zeros(len(ref))
    ck.call_variant("np", A, s, out, len(ref), A.shape[1], 5)
    np.testing.assert_allclose(out, ref, atol=1e-12)
    assert rt.decisions == []            # forced: no profitability ask
    (call,) = rt.shards
    assert (call["lo"], call["hi"], call["tile"]) == (0, len(ref), 4)
    assert call["written"] == ck.pfor_config.written
    assert "out" in call["written"]
    assert call["sliceable"] == tuple(call["body"].__sliceable__)
    assert "out" in call["sliceable"]
    assert call["est_flops"] == ck.pfor_config.estimated_flops > 0


@pytest.mark.parametrize("case", ["no_runtime", "force_sequential",
                                  "single_iteration", "unprofitable"])
def test_pfor_run_stays_on_caller(case):
    """Every way ``__pfor_run`` keeps a loop on the caller: one body
    call over the whole range, nothing sent to the runtime."""
    rt = _RecordingRT(profitable=case != "unprofitable")
    cfg = PforConfig(runtime=None if case == "no_runtime" else rt,
                     force_sequential=case == "force_sequential")
    cfg.estimated_flops = 5e6
    hi = 1 if case == "single_iteration" else 12
    data = np.arange(12.0)
    out = np.zeros(12)
    calls = []

    def body(lo, hi):
        calls.append((lo, hi))
        out[lo:hi] = data[lo:hi] * 3.0

    body.__sliceable__ = ("out",)
    cfg.make_runner()(body, 0, hi, None)
    assert calls == [(0, hi)]
    np.testing.assert_array_equal(out[:hi], data[:hi] * 3.0)
    assert rt.shards == []
    if case == "unprofitable":
        # asked once, with the broadcast/sliced payload split
        assert len(rt.decisions) == 1
        flops, bcast, n_chunks, sliced = rt.decisions[0]
        assert flops == 5e6 and n_chunks == 4
        assert (bcast, sliced) == (data.nbytes, out.nbytes)
    else:
        assert rt.decisions == []


class _SubmitGetOnly:
    """A task runtime without the pfor protocol (submit/get only)."""

    def submit(self, fn, *args):
        return fn(*args)

    def get(self, ref):
        return ref


class _ShardsOnly:
    def pfor_shards(self, body, lo, hi, tile, *, written, sliceable,
                    est_flops):
        body(lo, hi)


class _DecideOnly:
    def distribute_profitable(self, flops, bcast_bytes, n_chunks, *,
                              sliced_bytes):
        return False


@pytest.mark.parametrize("bad,missing", [
    (_SubmitGetOnly(), "pfor_shards, distribute_profitable"),
    (_ShardsOnly(), "distribute_profitable"),
    (_DecideOnly(), "pfor_shards"),
])
def test_compile_kernel_refuses_runtime_without_protocol(bad, missing):
    with pytest.raises(TypeError) as ei:
        compile_kernel(_recurrence, runtime=bad)
    msg = str(ei.value)
    assert type(bad).__name__ in msg
    assert f"lacks {missing}:" in msg


def test_stap_pipeline_correctness():
    from benchmarks.stap import (FFT_SIZE, make_data, stap_kernel,
                                 stap_ref)

    cubes, sv, mf, out = make_data(n_cubes=4)
    out_ref = out.copy()
    stap_ref(cubes, sv, mf, out_ref, 4, FFT_SIZE)
    ck = compile_kernel(stap_kernel)
    out_got = out.copy()
    ck.call_variant("np", cubes, sv, mf, out_got, 4, FFT_SIZE)
    np.testing.assert_allclose(out_got, out_ref, atol=1e-9)
    # the cube loop must be recognized as a distributable pfor
    assert ck.sched.has_pfor
