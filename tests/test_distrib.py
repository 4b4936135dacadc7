"""Cluster-runtime tests: serialization, placement, multi-process DAG,
pfor sharding vs sequential, worker-kill recovery, shared cache."""

import os
import threading
import time

import numpy as np
import pytest

from repro.core.compiler import compile_kernel
from repro.core import cost
from repro.distrib import (ClusterRuntime, ClusterTaskError, DeviceProfile,
                           PlacementScheduler, PlacementWeights, dumps_fn,
                           loads_fn)
from repro.distrib.objects import TaskSpec, ClusterRef
from repro.distrib.placement import WorkerView


# ---------------------------------------------------------------------------
# serialization (no processes involved)
# ---------------------------------------------------------------------------

def test_serialize_closure_roundtrip():
    data = np.arange(10.0)
    out = np.zeros(10)

    def body(lo, hi):
        for i in range(lo, hi):
            out[i] = data[i] * 3.0

    fn = loads_fn(dumps_fn(body))
    fn(0, 10)
    # the rebuilt closure wrote into its own fresh copy, not ours
    assert np.all(out == 0.0)
    copies = dict(zip(fn.__code__.co_freevars,
                      [c.cell_contents for c in fn.__closure__]))
    assert np.allclose(copies["out"], data * 3.0)


def test_serialize_module_global_and_defaults():
    def f(x, k=4):
        return np.sqrt(x) + k

    g = loads_fn(dumps_fn(f))
    assert g(9.0) == 7.0
    assert g(9.0, k=0) == 3.0


def test_serialize_kwonly_defaults():
    def f(x, *, scale=2.0):
        return x * scale

    g = loads_fn(dumps_fn(f))
    assert g(3.0) == 6.0
    assert g(3.0, scale=0.5) == 1.5


def test_serialize_nested_pfor_runs_sequentially():
    out = np.zeros(4)

    def outer(lo, hi):
        def inner(l2, h2):
            for i in range(l2, h2):
                out[i] = i
        __pfor_run(inner, lo, hi, None)  # noqa: F821 — worker-injected

    # on the source side __pfor_run is a global we never defined; ship
    # with the sentinel and the worker substitutes a sequential runner
    outer.__globals__["__pfor_run"] = lambda b, lo, hi, t: b(lo, hi)
    g = loads_fn(dumps_fn(outer))
    g(0, 4)
    copies = dict(zip(g.__code__.co_freevars,
                      [c.cell_contents for c in g.__closure__]))
    assert np.allclose(copies["out"], [0, 1, 2, 3])


# ---------------------------------------------------------------------------
# placement scoring (pure functions)
# ---------------------------------------------------------------------------

def _view(wid, gflops, outstanding=0, resident=None, has_gpu=False):
    return WorkerView(wid, DeviceProfile(wid=wid, gflops=gflops,
                                         has_gpu=has_gpu),
                      outstanding, resident or {})


def _task(args=(), device_pref=""):
    return TaskSpec(1, "fn", b"", tuple(args),
                    ClusterRef(1), device_pref=device_pref)


def test_placement_prefers_capability():
    sched = PlacementScheduler()
    views = [_view(0, gflops=10.0), _view(1, gflops=40.0)]
    assert sched.place(_task(), views) == 1


def test_placement_locality_beats_capability():
    sched = PlacementScheduler()
    ref = ClusterRef(7)
    views = [_view(0, gflops=10.0, resident={7: 1 << 20}),
             _view(1, gflops=20.0)]
    assert sched.place(_task(args=(ref,)), views,
                       arg_bytes={7: 1 << 20}) == 0


def test_placement_load_pushes_away():
    sched = PlacementScheduler()
    views = [_view(0, gflops=10.0, outstanding=8),
             _view(1, gflops=10.0, outstanding=0)]
    assert sched.place(_task(), views) == 1


def test_placement_gpu_preference():
    sched = PlacementScheduler()
    views = [_view(0, gflops=50.0), _view(1, gflops=5.0, has_gpu=True)]
    assert sched.place(_task(device_pref="gpu"), views) == 1
    assert sched.place(_task(), views) == 0


def test_proportional_chunks_follow_weights():
    chunks = PlacementScheduler.proportional_chunks(0, 90, [1.0, 2.0])
    assert [len(c) for c in chunks] == [30, 60]
    assert chunks[0].start == 0 and chunks[-1].stop == 90
    # degenerate weights still cover the range exactly once
    chunks = PlacementScheduler.proportional_chunks(5, 8, [1e-12, 1.0])
    assert sum(len(c) for c in chunks) == 3


def test_cluster_profitability_uses_profiles():
    fleet = [DeviceProfile(wid=i, gflops=50.0, transport_mbs=500.0)
             for i in range(4)]
    # tiny kernel: overhead dominates → stay local
    assert not cost.cluster_distribute_profitable(
        1e5, 1 << 20, fleet, n_chunks=4, local_gflops=50.0)
    # huge kernel, small payload → distribute
    assert cost.cluster_distribute_profitable(
        5e10, 1 << 20, fleet, n_chunks=4, local_gflops=50.0)
    # a slow head flips the tiny-kernel decision
    assert not cost.cluster_distribute_profitable(
        1e5, 1 << 30, fleet, n_chunks=4, local_gflops=50.0)
    assert not cost.cluster_distribute_profitable(1e9, 0, [], 1)


# ---------------------------------------------------------------------------
# live cluster (2 worker processes)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster():
    rt = ClusterRuntime(workers=2)
    yield rt
    rt.shutdown()


def _double(x):
    return x * 2


def _make(n):
    return np.arange(float(n))


def test_cluster_submit_get_chain(cluster):
    a = cluster.submit(_double, 21)
    b = cluster.submit(_double, a)
    assert cluster.get(b, timeout=30) == 84


def test_task_returning_none_is_distinguishable(cluster):
    def ret_none(x):
        return None

    ref = cluster.submit(ret_none, 1)
    assert cluster.get(ref, timeout=30) is None
    # and it can feed a downstream task like any other value
    def is_none(v):
        return v is None

    assert cluster.get(cluster.submit(is_none, ref), timeout=30)


def test_pfor_releases_chunk_bookkeeping(cluster):
    out = np.zeros(16)
    data = np.arange(16.0)

    def make_body(out, data):
        def body(lo, hi):
            for i in range(lo, hi):
                out[i] = data[i] + 1.0
        return body

    before = cluster.plane.stats()["objects"]
    cluster.pfor_shards(make_body(out, data), 0, 16, written=("out",))
    assert np.allclose(out, data + 1.0)
    # chunk specs/objects are consumed and dropped — a serving loop
    # calling pfor forever keeps the head's memory flat
    assert cluster.plane.stats()["objects"] == before


def test_cluster_task_error_surfaces(cluster):
    def boom(x):
        raise ValueError("nope")

    ref = cluster.submit(boom, 1)
    with pytest.raises(ClusterTaskError):
        cluster.get(ref, timeout=30)


def test_upstream_error_poisons_dependents(cluster):
    def boom(x):
        raise ValueError("upstream boom")

    a = cluster.submit(boom, 1)
    b = cluster.submit(_double, a)
    with pytest.raises(ClusterTaskError, match="upstream"):
        cluster.get(b, timeout=60)


def _fail_first_attempt(marker, x):
    # the task runs in a worker process: the marker file is the only
    # state its attempts share
    if not os.path.exists(marker):
        open(marker, "w").close()
        raise RuntimeError("first attempt fails")
    return x * 2


def test_task_retry_succeeds_after_first_attempt_fails(cluster, tmp_path):
    assert cluster.max_attempts >= 2
    marker = str(tmp_path / "attempted")
    before = cluster.stats()["resubmits"]
    ref = cluster.submit(_fail_first_attempt, marker, 21)
    assert cluster.get(ref, timeout=30) == 42
    assert os.path.exists(marker)
    assert cluster.stats()["resubmits"] == before + 1


def _sleep_then(seconds, x):
    time.sleep(seconds)
    return x


def test_submit_returns_before_task_finishes(cluster):
    t0 = time.perf_counter()
    ref = cluster.submit(_sleep_then, 1.0, 7)
    assert time.perf_counter() - t0 < 0.5   # submission does not block
    ready, pending = cluster.wait([ref], num_returns=1, timeout=0.0)
    assert ready == [] and pending == [ref]
    assert cluster.get(ref, timeout=30) == 7
    assert time.perf_counter() - t0 >= 1.0


def test_cluster_large_result_stays_remote_until_get(cluster):
    ref = cluster.submit(_make, 200_000)
    cluster.wait([ref], num_returns=1, timeout=30)
    assert cluster.plane.meta(ref.oid).state == "remote"
    v = cluster.get(ref, timeout=30)
    assert v.shape == (200_000,)
    assert cluster.plane.meta(ref.oid).state == "head"


def test_cluster_pfor_matches_sequential(cluster):
    rng = np.random.default_rng(3)
    data = rng.normal(size=(40, 64))
    out = np.zeros(40)
    out_seq = np.zeros(40)

    def make_body(out, data):
        def body(lo, hi):
            for i in range(lo, hi):
                out[i] = float(data[i].sum()) * 2.0
        return body

    make_body(out_seq, data)(0, 40)
    cluster.pfor_shards(make_body(out, data), 0, 40, written=("out",))
    assert np.allclose(out, out_seq)


def test_compiled_kernel_pfor_shards_match_sequential(cluster):
    # inner recurrence on a privatized vector keeps the row loop a real
    # pfor (a pure elementwise kernel would absorb into one statement)
    def mini_stap(A: "ndarray[f64,2]", s: "ndarray[f64,1]",
                  out: "ndarray[f64,1]", N: int, M: int, iters: int):
        for i in range(0, N):
            w = 0.1 * s[0:M]
            for it in range(0, iters):
                w = w + 0.1 * (s[0:M] - A[i, 0:M] * w[0:M])
            out[i] = np.dot(w[0:M], A[i, 0:M])

    rng = np.random.default_rng(0)
    A = rng.normal(size=(32, 16))
    s = rng.normal(size=16)
    out_seq = np.zeros(32)
    mini_stap(A, s, out_seq, 32, 16, 12)

    ck = compile_kernel(mini_stap, runtime=cluster)
    assert ck.sched.has_pfor
    ck.pfor_config.distribute_threshold = 0  # force the cluster tier
    out = np.zeros(32)
    ck.call_variant("np", A, s, out, 32, 16, 12)
    assert np.allclose(out, out_seq, atol=1e-12)
    assert cluster.stats()["pfor_runs"] >= 1


def test_small_kernel_stays_local_by_profitability(cluster):
    def tiny(out: "ndarray[f64,1]", N: int):
        for i in range(0, N):
            out[i] = i * 1.0

    ck = compile_kernel(tiny, runtime=cluster)
    before = cluster.stats()["chunks_dispatched"]
    out = np.zeros(8)
    ck.call_variant("np", out, 8)
    assert np.allclose(out, np.arange(8.0))
    # device-profile cost model keeps micro-kernels off the wire
    assert cluster.stats()["chunks_dispatched"] == before


# -- failure drills (own runtimes: they mutate the fleet) -------------------

def test_worker_kill_lineage_replay():
    rt = ClusterRuntime(workers=2)
    try:
        ref = rt.submit(_make, 300_000)
        rt.wait([ref], num_returns=1, timeout=30)
        meta = rt.plane.meta(ref.oid)
        assert meta.state == "remote"
        rt.kill_worker(meta.owner)
        v = rt.get(ref, timeout=60)
        assert np.array_equal(v, np.arange(300_000.0))
        assert rt.stats()["lineage_replays"] >= 1
        assert rt.stats()["worker_deaths"] == 1
    finally:
        rt.shutdown()


def _plus_one(a):
    return a + 1.0


def test_worker_kill_replays_a_chain_of_three():
    """A one-worker fleet loses every object of a three-task chain with
    its worker: reading the tip replays the whole chain, transitively,
    on the respawned worker."""
    rt = ClusterRuntime(workers=1)
    try:
        a = rt.submit(_make, 300_000)
        b = rt.submit(_plus_one, a)
        c = rt.submit(_plus_one, b)
        rt.wait([a, b, c], num_returns=3, timeout=30)
        (owner,) = {rt.plane.meta(r.oid).owner for r in (a, b, c)}
        assert all(rt.plane.meta(r.oid).state == "remote"
                   for r in (a, b, c))
        rt.kill_worker(owner)
        v = rt.get(c, timeout=60)
        assert np.array_equal(v, np.arange(300_000.0) + 2.0)
        st = rt.stats()
        assert st["worker_deaths"] == 1
        assert st["lineage_replays"] >= 3
    finally:
        rt.shutdown()


def test_worker_kill_during_pfor_recovers():
    rt = ClusterRuntime(workers=2)
    try:
        rng = np.random.default_rng(1)
        data = rng.normal(size=(120, 2000))
        out = np.zeros(120)

        def make_body(out, data):
            def body(lo, hi):
                for i in range(lo, hi):
                    s = 0.0
                    for _ in range(40):
                        s = s + float(data[i].sum())
                    out[i] = s
            return body

        killer = threading.Timer(0.1, rt.kill_worker)
        killer.start()
        rt.pfor_shards(make_body(out, data), 0, 120, tile=10,
                       written=("out",))
        killer.cancel()
        assert np.allclose(out, 40 * data.sum(axis=1))
    finally:
        rt.shutdown()


def test_respawn_restores_fleet_size():
    rt = ClusterRuntime(workers=2)
    try:
        rt.kill_worker()
        deadline = time.time() + 10
        while rt.workers_alive() < 2 and time.time() < deadline:
            time.sleep(0.05)
        assert rt.workers_alive() == 2
    finally:
        rt.shutdown()


# -- serving-loop soak: blob cache + slicing under fault injection -----------

def _soak_kernel(A: "ndarray[f64,2]", s: "ndarray[f64,1]",
                 out: "ndarray[f64,1]", N: int, M: int, iters: int):
    for i in range(0, N):
        w = 0.1 * s[0:M]
        for it in range(0, iters):
            w = w + 0.1 * (s[0:M] - A[i, 0:M] * w[0:M])
        out[i] = np.dot(w[0:M], A[i, 0:M])


def test_soak_serving_loop_blob_cache_flat_memory_and_kill():
    """A serving loop calling one cluster-compiled kernel 50×, with a
    worker SIGKILLed mid-run: results stay correct, the head's memory
    stays flat (no chunk bookkeeping accumulates), the body blob ships
    once and every later call is a cache hit, and unchanged broadcast
    cells stop moving after their first ship."""
    rt = ClusterRuntime(workers=2)
    try:
        rng = np.random.default_rng(42)
        N, M, iters = 32, 16, 8
        A = rng.normal(size=(N, M)) * 0.1
        s = rng.normal(size=M)
        out_ref = np.zeros(N)
        _soak_kernel(A, s, out_ref, N, M, iters)

        ck = compile_kernel(_soak_kernel, runtime=rt)
        assert ck.sched.has_pfor
        ck.pfor_config.distribute_threshold = 0  # force the cluster tier
        baseline = None
        for call in range(50):
            if call == 10:
                assert rt.kill_worker() is not None
            out = np.zeros(N)
            ck.call_variant("np", A, s, out, N, M, iters)
            assert np.allclose(out, out_ref, atol=1e-12), f"call {call}"
            if call == 2:
                st = rt.stats()
                baseline = (st["plane"]["objects"], st["tasks"])
        st = rt.stats()
        assert (st["plane"]["objects"], st["tasks"]) == baseline
        assert st["blob_misses"] == 1
        assert st["blob_hits"] == 49
        assert st["cells_skipped"] > st["cells_shipped"]
        assert st["sliced_args"] > 0
        assert st["worker_deaths"] == 1
    finally:
        rt.shutdown()


# -- shared variant cache ----------------------------------------------------

def _cache_kernel(out: "ndarray[f64,1]", N: int):
    for i in range(0, N):
        out[i] = i * 3.0


def test_shared_cache_warm_start_across_runtimes(tmp_path):
    shared = str(tmp_path / "fleet-cache")
    rt1 = ClusterRuntime(workers=1, cache_dir=shared)
    try:
        ck1 = rt1.compile(_cache_kernel)
        out = np.zeros(4)
        ck1.call_variant("np", out, 4)
        assert rt1.variant_cache.stats.puts >= 1
    finally:
        rt1.shutdown()

    rt2 = ClusterRuntime(workers=1, cache_dir=shared)
    try:
        ck2 = rt2.compile(_cache_kernel)
        assert ck2.from_cache
        tel = rt2.telemetry()["cache"]
        assert tel["hits"] > 0, tel
        out = np.zeros(4)
        ck2.call_variant("np", out, 4)
        assert np.allclose(out, np.arange(4.0) * 3)
    finally:
        rt2.shutdown()


def test_variant_cache_shared_dir_backend(tmp_path):
    from repro.profiler.cache import VariantCache

    shared = str(tmp_path / "shared")
    c1 = VariantCache(str(tmp_path / "local1"), shared_dir=shared)
    ck = compile_kernel(_cache_kernel, cache=c1)
    assert c1.stats.shared_puts >= 1

    # a different node: empty local tier, same shared store
    c2 = VariantCache(str(tmp_path / "local2"), shared_dir=shared)
    ck2 = compile_kernel(_cache_kernel, cache=c2)
    assert ck2.from_cache
    assert c2.stats.shared_hits >= 1
    assert c2.stats.hits >= 1


# -- telemetry ---------------------------------------------------------------

def test_profiles_and_telemetry(cluster):
    profs = cluster.profiles()
    assert len(profs) == 2
    for p in profs:
        assert p.gflops > 0
        assert p.cpus >= 1
    tel = cluster.telemetry()
    assert tel["workers"] == 2
    assert tel["local_gflops"] > 0
    assert len(tel["profiles"]) == 2
