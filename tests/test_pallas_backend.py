"""Pallas backend end-to-end: pattern-matched pfor units route onto the
seed Pallas kernels, roofline-priced against np/jnp, degrading down the
``TaskSpec.alt`` chain when a lowering fails — counted, not crashed.

Interpret mode runs everywhere (CPU CI); the kernels' compiles for the
chip are checked in ``tests/test_tpu_compile.py``.
"""

import numpy as np
import pytest

# imported at module scope so ClusterRuntime worker forks inherit the
# already-loaded jax (a cold per-worker import costs seconds)
import jax

jax.config.update("jax_enable_x64", True)

from repro.core import cost
from repro.core.compiler import compile_kernel
from repro.distrib import ClusterRuntime
from repro.kernels import api


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    monkeypatch.delenv("REPRO_DISTRIB_SIM_GPU", raising=False)
    monkeypatch.delenv("REPRO_PALLAS_CHAOS", raising=False)


# ---------------------------------------------------------------------------
# shaped kernels (the prelude keeps the single np.dot statement from
# being absorbed into a top-level raised unit — it must stay a pfor)
# ---------------------------------------------------------------------------

def mm_kernel(A: "ndarray[f64,2]", B: "ndarray[f64,2]",
              C: "ndarray[f64,2]", n: int, k: int, m: int):
    for i in range(0, n):
        r = 2.0 * A[i, 0:k]
        C[i, 0:m] = np.dot(r, B[0:k, 0:m])


def attn_kernel(Q: "ndarray[f64,2]", K: "ndarray[f64,2]",
                V: "ndarray[f64,2]", O: "ndarray[f64,2]",
                n: int, t: int, d: int):
    for i in range(0, n):
        s = np.dot(K[0:t, 0:d], Q[i, 0:d])
        p = np.exp(s)
        o = np.dot(p, V[0:t, 0:d])
        O[i, 0:d] = o / np.sum(p)


def scan_kernel(X: "ndarray[f64,2]", Y: "ndarray[f64,2]",
                n: int, L: int):
    for i in range(0, n):
        h = 0.0
        for t in range(0, L):
            h = 0.9 * h + X[i, t]
            Y[i, t] = h


def scan_kernel_param(X: "ndarray[f64,2]", Y: "ndarray[f64,2]",
                      c: float, n: int, L: int):
    for i in range(0, n):
        h = 0.0
        for t in range(0, L):
            h = c * h + X[i, t]
            Y[i, t] = h


def _mm_ref(A, B, n, k, m):
    C = np.zeros((n, m))
    mm_kernel(A, B, C, n, k, m)
    return C


# ---------------------------------------------------------------------------
# codegen: matched units carry a pallas twin, unmatched units do not
# ---------------------------------------------------------------------------

def test_matmul_shape_gets_pallas_twin():
    ck = compile_kernel(mm_kernel)
    src = ck.source("np")
    assert "def __pfor_body_0__pallas(" in src
    assert "__plk.matmul(" in src
    assert "__pfor_body_0.__pallas__ = __pfor_body_0__pallas" in src
    assert ck.pfor_twin_units().get("pallas") == [0]
    # the jnp twin still rides along (the degradation chain's middle)
    assert "def __pfor_body_0__jnp(" in src


def test_attention_shape_gets_pallas_twin():
    src = compile_kernel(attn_kernel).source("np")
    assert "__plk.attention_rows(" in src


def test_scan_shape_gets_pallas_twin():
    src = compile_kernel(scan_kernel).source("np")
    assert "__plk.scan_rows(" in src
    # the statically-known coefficient is baked into the call
    assert "0.9" in src


def test_unshaped_body_gets_no_pallas_twin():
    def plain_kernel(x: "ndarray[f64,2]", outY: "ndarray[f64,1]",
                     n: int, m: int):
        for i in range(0, n):
            w = 0.5 * x[i, 0:m]
            outY[i] = np.dot(w[0:m], x[i, 0:m])

    ck = compile_kernel(plain_kernel)
    assert "__plk" not in ck.source("np")
    assert "pallas" not in ck.pfor_twin_units()


def test_pallas_twin_matches_np_body_inprocess():
    """Run the captured pallas twin directly over the full range —
    equivalence without any processes (interpret mode on CPU)."""
    bodies = {}

    class FakeRT:
        def pfor_shards(self, body, lo, hi, tile, **kw):
            bodies["np"] = body
            bodies["pallas"] = body.__pallas__
            body.__pallas__(lo, hi)

        def distribute_profitable(self, *a, **k):
            return True

    ck = compile_kernel(mm_kernel, runtime=FakeRT())
    ck.pfor_config.distribute_threshold = 0
    rng = np.random.default_rng(0)
    n, k, m = 12, 8, 6
    A, B = rng.normal(size=(n, k)), rng.normal(size=(k, m))
    C = np.zeros((n, m))
    ck.call_variant("np", A, B, C, n, k, m)
    assert np.allclose(C, _mm_ref(A, B, n, k, m), atol=1e-8)
    assert bodies["pallas"].__backend__ == "pallas"


# ---------------------------------------------------------------------------
# cost: the roofline prices pallas above jnp only where the fusion win
# is real
# ---------------------------------------------------------------------------

def _prof(gflops=50.0, gpu=False, gpu_gflops=0.0, kind=""):
    from repro.distrib import DeviceProfile

    return DeviceProfile(wid=0, gflops=gflops, membw_gbs=10.0,
                         has_gpu=gpu, gpu_gflops=gpu_gflops,
                         gpu_kind=kind)


def test_pallas_prices_above_jnp_when_matched():
    sim = _prof(gpu=True, gpu_gflops=200.0, kind="sim")
    real = _prof(gpu=True, gpu_gflops=2000.0, kind="cuda")
    cpu = _prof()
    both = ("jnp", "pallas")
    # matched unit on a sim GPU: the fused kernel wins outright
    assert cost.pick_chunk_backend(1e8, 1e6, sim,
                                   candidates=both) == "pallas"
    # unmatched unit (no pallas candidate): jnp as before
    assert cost.pick_chunk_backend(1e8, 1e6, sim,
                                   candidates=("jnp",)) == "jnp"
    # CPU-only worker: infeasible, np regardless of candidates
    assert cost.pick_chunk_backend(1e8, 1e6, cpu,
                                   candidates=both) == "np"
    # real device, tiny chunk: even the smaller pallas launch overhead
    # buries the work → np
    assert cost.pick_chunk_backend(1e4, 1e3, real,
                                   candidates=both) == "np"
    # real device, big chunk: pallas amortizes and wins
    assert cost.pick_chunk_backend(5e9, 1e6, real,
                                   candidates=both) == "pallas"


# ---------------------------------------------------------------------------
# the CI smoke contract: sim-GPU fleet routes matmul chunks to pallas,
# results equal to the np-only control
# ---------------------------------------------------------------------------

N, K, M = 32, 12, 10


def _run_fleet(ck, A, B, *, sim_gpus=(0, 1), env=None, monkeypatch=None):
    if env:
        for kk, vv in env.items():
            monkeypatch.setenv(kk, vv)
    rt = ClusterRuntime(workers=2, sim_gpu_workers=sim_gpus)
    try:
        ck.pfor_config.runtime = rt
        ck.pfor_config.workers = 2
        ck.pfor_config.distribute_threshold = 0
        C = np.zeros((N, M))
        ck.call_variant("np", A, B, C, N, K, M)
        return C, rt.stats()
    finally:
        rt.shutdown()
        ck.pfor_config.runtime = None


def test_matmul_routes_to_pallas_on_sim_gpu_fleet():
    rng = np.random.default_rng(1)
    A, B = rng.normal(size=(N, K)), rng.normal(size=(K, M))
    ck = compile_kernel(mm_kernel)

    got, st = _run_fleet(ck, A, B, sim_gpus=(0, 1))
    assert np.allclose(got, _mm_ref(A, B, N, K, M), atol=1e-8)
    ran = st["chunks_executed"]
    assert ran.get("pallas", 0) > 0
    assert st["pallas_chunks"] > 0
    assert st["pallas_fallbacks"] == 0
    # worker-side kernel telemetry piggybacked on the done messages
    assert st["pallas_calls"] > 0
    assert st["pallas_interpret_calls"] == st["pallas_calls"]  # CPU sim
    (mix,) = st["unit_backend"].values()
    assert "pallas" in mix

    # np-only control on a CPU fleet: identical results
    ctrl, st2 = _run_fleet(ck, A, B, sim_gpus=())
    assert np.allclose(ctrl, got, atol=1e-12)
    assert st2["chunks_executed"].get("pallas", 0) == 0


def test_pallas_chaos_degrades_counted_not_crashed(monkeypatch):
    """REPRO_PALLAS_CHAOS=fail makes every worker-side kernel call
    raise: chunks must degrade pallas → jnp (→ np) with the fallback
    counted and the results still correct."""
    rng = np.random.default_rng(2)
    A, B = rng.normal(size=(N, K)), rng.normal(size=(K, M))
    ck = compile_kernel(mm_kernel)
    got, st = _run_fleet(ck, A, B, sim_gpus=(0, 1),
                         env={"REPRO_PALLAS_CHAOS": "fail"},
                         monkeypatch=monkeypatch)
    assert np.allclose(got, _mm_ref(A, B, N, K, M), atol=1e-8)
    assert st["pallas_fallbacks"] > 0
    assert st["chunks_executed"].get("pallas", 0) == 0
    assert st["chunks_executed"].get("jnp", 0) \
        + st["chunks_executed"].get("np", 0) > 0


def test_runtime_infeasible_scan_coeff_degrades(monkeypatch):
    """A scan whose coefficient is only known at run time (VParam)
    still gets a pallas twin; a value outside (0, 1) raises the
    lowering-infeasible error on the worker and the chunk degrades
    organically down the alt chain."""
    ck = compile_kernel(scan_kernel_param)
    assert "__plk.scan_rows(" in ck.source("np")
    rng = np.random.default_rng(3)
    n, L = 24, 16
    X = rng.normal(size=(n, L))
    ref = np.zeros((n, L))
    scan_kernel_param(X, ref, 1.5, n, L)    # c ≥ 1: kernel must refuse
    rt = ClusterRuntime(workers=2, sim_gpu_workers=(0, 1))
    try:
        ck.pfor_config.runtime = rt
        ck.pfor_config.workers = 2
        ck.pfor_config.distribute_threshold = 0
        Y = np.zeros((n, L))
        ck.call_variant("np", X, Y, 1.5, n, L)
        assert np.allclose(Y, ref, atol=1e-8)
        st = rt.stats()
        assert st["pallas_fallbacks"] > 0
        assert st["chunks_executed"].get("pallas", 0) == 0
    finally:
        rt.shutdown()
        ck.pfor_config.runtime = None
