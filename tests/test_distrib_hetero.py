"""Heterogeneous CPU/GPU chunk routing (per-unit backend variants).

Covers the whole seam: codegen's backend-tagged twin bodies, the
(unit, backend, worker-profile) pricing table in core.cost, simulated-GPU
device profiles, placement routing by ``device_pref``, the mixed-fleet
equivalence grid (np-only / jnp-only / mixed clusters on one compiled
pfor), and the recv/send close-race regression (the tracked
``'NoneType' cannot be interpreted as an integer`` flaky).
"""

import multiprocessing as mp
import threading
import time

import numpy as np
import pytest

# imported at module scope so ClusterRuntime worker forks inherit the
# already-loaded jax (a cold per-worker import costs seconds)
import jax

jax.config.update("jax_enable_x64", True)

from repro.core import cost
from repro.core.compiler import compile_kernel
from repro.distrib import ClusterRuntime, DeviceProfile
from repro.distrib.cluster import _WorkerHandle
from repro.distrib.device import measure_profile, sim_gpu_for
from repro.distrib.objects import TaskSpec, ClusterRef
from repro.distrib.placement import (PlacementScheduler, PlacementWeights,
                                     WorkerView)


@pytest.fixture(autouse=True)
def _no_ambient_sim_gpu(monkeypatch):
    """Fleet composition in these tests is kwarg-driven; an ambient
    ``REPRO_DISTRIB_SIM_GPU`` (e.g. the CI hetero step) must not leak
    into the np-only cases through worker-process environments."""
    monkeypatch.delenv("REPRO_DISTRIB_SIM_GPU", raising=False)


def hetero_kernel(x: "ndarray[f64,2]", y: "ndarray[f64,2]",
                  outY: "ndarray[f64,1]", n: int, m: int, iters: int):
    for i in range(0, n):
        w = 0.5 * y[i, 0:m]
        for t in range(0, iters):
            w = w + 0.1 * (x[i, 0:m] - w)
        outY[i] = np.dot(w[0:m], y[i, 0:m])


def _make_data(n=12, m=6, seed=3):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, m)), rng.normal(size=(n, m)), np.zeros(n)


def _reference(x, y, n, m, iters):
    out = np.zeros(n)
    hetero_kernel(x, y, out, n, m, iters)
    return out


# ---------------------------------------------------------------------------
# codegen: per-unit backend twins
# ---------------------------------------------------------------------------

def test_codegen_emits_backend_tagged_twins():
    ck = compile_kernel(hetero_kernel)
    src = ck.source("np")
    assert "__pfor_body_0.__backend__ = 'np'" in src
    assert "def __pfor_body_0__jnp(" in src
    assert "__pfor_body_0__jnp.__backend__ = 'jnp'" in src
    assert "__pfor_body_0.__jnp__ = __pfor_body_0__jnp" in src
    # twin computes through __jxp, np body through xp
    assert "__jxp.dot(" in src and "xp.dot(" in src
    # both bodies carry the same sliceability stamp
    assert src.count(".__sliceable__ = ('x', 'y', 'outY')") == 2 or \
        src.count(".__sliceable__ =") == 2
    assert ck.pfor_jnp_units() == [0]
    assert ck.stats()["pfor_jnp_units"] == 1


def test_jnp_twin_matches_np_body_inprocess():
    """Run the captured twin directly over the full range — bitwise-close
    equivalence without any processes."""
    got_bodies = {}

    class FakeRT:
        def pfor_shards(self, body, lo, hi, tile, written=(),
                        sliceable=(), est_flops=0.0):
            got_bodies["np"] = body
            got_bodies["jnp"] = body.__jnp__
            got_bodies["est_flops"] = est_flops
            body.__jnp__(lo, hi)

        def distribute_profitable(self, *a, **k):
            return True

    ck = compile_kernel(hetero_kernel, runtime=FakeRT())
    ck.pfor_config.distribute_threshold = 0
    x, y, out = _make_data()
    ref = _reference(x, y, 12, 6, 5)
    ck.call_variant("np", x, y, out, 12, 6, 5)
    assert np.allclose(out, ref, atol=1e-8)
    assert got_bodies["np"].__backend__ == "np"
    assert got_bodies["jnp"].__backend__ == "jnp"
    # the dispatcher's FLOP estimate reached the sharder
    assert got_bodies["est_flops"] > 0


def numpy_local_kernel(A: "ndarray[f64,2]", out: "ndarray[f64,1]",
                       n: int, m: int):
    for i in range(0, n):
        t = 1.0 * A[i, 0:m]          # pure-numpy local (no jnp op)
        t[0:m] = t[0:m] * 2.0        # partial store → .at[] in the twin
        out[i] = np.dot(t[0:m], A[i, 0:m])


def test_twin_converts_numpy_locals_before_at_stores():
    """A body local defined by pure numpy arithmetic over captured
    arrays must still be a jnp value in the twin — otherwise the .at[]
    partial store crashes every jnp-routed chunk (review finding)."""
    ck = compile_kernel(numpy_local_kernel)
    src = ck.source("np")
    assert "__pfor_body_0__jnp" in src
    body = {}

    class FakeRT:
        def pfor_shards(self, b, lo, hi, tile, **kw):
            body["jnp"] = b.__jnp__
            b.__jnp__(lo, hi)

        def distribute_profitable(self, *a, **k):
            return True

    ck.pfor_config.runtime = FakeRT()
    ck.pfor_config.distribute_threshold = 0
    rng = np.random.default_rng(0)
    A = rng.normal(size=(7, 4))
    ref = np.zeros(7)
    numpy_local_kernel(A, ref, 7, 4)
    out = np.zeros(7)
    ck.call_variant("np", A, out, 7, 4)
    assert np.allclose(out, ref, atol=1e-8)


def test_proportional_chunks_keep_alignment_with_weights():
    """A worker whose share rounds to zero must not shift later chunks
    onto another view's backend (review finding): drop_empty=False
    returns one range per weight, empties included."""
    ranges = PlacementScheduler.proportional_chunks(
        0, 2, [1.0, 100.0, 1.0], drop_empty=False)
    assert len(ranges) == 3
    assert [len(r) for r in ranges].count(0) >= 1
    assert sum(len(r) for r in ranges) == 2
    # the big-weight view keeps its own (middle) slot
    assert len(ranges[1]) == 2
    # default behavior unchanged for existing callers
    assert all(len(r) > 0 for r in PlacementScheduler.proportional_chunks(
        0, 2, [1.0, 100.0, 1.0]))


def test_twin_skipped_for_opaque_bodies():
    """A pfor whose body contains a black-box statement keeps an np-only
    body (no twin, no __jnp__)."""

    def opaque_body(outY: "ndarray[f64,1]", n: int):
        for i in range(0, n):
            outY[i] = float(np.random.default_rng(i).normal())

    ck = compile_kernel(opaque_body)
    src = ck.source("np")
    if "__pfor_body_0" in src:       # parallel or not, never a twin
        assert "__jnp__" not in src
    assert ck.pfor_jnp_units() == []


# ---------------------------------------------------------------------------
# cost: the (unit, backend, worker-profile) pricing table
# ---------------------------------------------------------------------------

def _prof(gflops=50.0, gpu=False, gpu_gflops=0.0, kind=""):
    return DeviceProfile(wid=0, gflops=gflops, membw_gbs=10.0,
                         has_gpu=gpu, gpu_gflops=gpu_gflops,
                         gpu_kind=kind)


def test_pick_chunk_backend_prices_cells():
    cpu = _prof()
    sim = _prof(gpu=True, gpu_gflops=200.0, kind="sim")
    real = _prof(gpu=True, gpu_gflops=2000.0, kind="cuda")
    # CPU-only worker never runs the twin
    assert cost.pick_chunk_backend(1e9, 1e6, cpu) == "np"
    # no twin available: np regardless of hardware
    assert cost.pick_chunk_backend(1e9, 1e6, real, allow_jnp=False) == "np"
    # simulated GPU prices without staging overhead → jnp even when tiny
    assert cost.pick_chunk_backend(1e4, 1e3, sim) == "jnp"
    # real GPU: launch overhead buries a tiny chunk …
    assert cost.pick_chunk_backend(1e4, 1e3, real) == "np"
    # … but a big chunk amortizes it
    assert cost.pick_chunk_backend(5e9, 1e6, real) == "jnp"
    # zero FLOP estimate degrades to capability tags
    assert cost.pick_chunk_backend(0.0, 0.0, real) == "jnp"


def test_unit_backend_table_and_effective_rates():
    cpu, sim = _prof(gflops=40.0), _prof(gflops=40.0, gpu=True,
                                         gpu_gflops=160.0, kind="sim")
    table = cost.unit_backend_table(1e8, 1e6, [cpu, sim])
    assert table == ["np", "jnp"]
    assert cost.backend_effective_gflops(cpu, "np") == 40.0
    assert cost.backend_effective_gflops(sim, "jnp") == 160.0


# ---------------------------------------------------------------------------
# device: simulated-GPU profiles
# ---------------------------------------------------------------------------

def test_sim_gpu_env_parsing(monkeypatch):
    monkeypatch.delenv("REPRO_DISTRIB_SIM_GPU", raising=False)
    assert not sim_gpu_for(0)
    monkeypatch.setenv("REPRO_DISTRIB_SIM_GPU", "all")
    assert sim_gpu_for(0) and sim_gpu_for(7)
    assert not sim_gpu_for(-1)          # the head never poses
    monkeypatch.setenv("REPRO_DISTRIB_SIM_GPU", "1")
    assert sim_gpu_for(1) and not sim_gpu_for(0)
    monkeypatch.setenv("REPRO_DISTRIB_SIM_GPU", "0,2")
    assert sim_gpu_for(0) and sim_gpu_for(2) and not sim_gpu_for(1)
    monkeypatch.setenv("REPRO_DISTRIB_SIM_GPU", "bogus")
    assert not sim_gpu_for(0)


def test_measure_profile_sim_pose(monkeypatch):
    monkeypatch.setenv("REPRO_DISTRIB_SIM_GPU_FACTOR", "3")
    p = measure_profile(2, sim_gpu=True)
    assert p.has_gpu and p.gpu_kind == "sim"
    assert p.gpu_gflops == pytest.approx(3 * p.gflops, rel=0.01)
    q = measure_profile(2, sim_gpu=False)
    assert not q.has_gpu and q.gpu_gflops == 0.0
    # profile survives the wire dict roundtrip with the new field
    r = DeviceProfile.from_dict(p.as_dict())
    assert r.gpu_gflops == p.gpu_gflops


# ---------------------------------------------------------------------------
# placement: device_pref routing
# ---------------------------------------------------------------------------

def _chunk_spec(pref):
    return TaskSpec(1, "chunk", None, (), ClusterRef(1), device_pref=pref)


def test_placement_routes_jnp_chunks_to_gpu_worker():
    sched = PlacementScheduler(PlacementWeights())
    views = [WorkerView(0, _prof(gflops=80.0)),
             WorkerView(1, _prof(gflops=40.0, gpu=True,
                                 gpu_gflops=160.0, kind="sim"))]
    assert sched.place(_chunk_spec("gpu"), views) == 1
    # np chunks steer away from the GPU worker even though it is loaded
    # lighter — its cycles are budgeted for the jnp chunks
    views[0].outstanding = 1
    assert sched.place(_chunk_spec("cpu"), views) == 0
    # no preference: capability wins as before
    views[0].outstanding = 0
    assert sched.place(_chunk_spec(""), views) == 0


# ---------------------------------------------------------------------------
# mixed-fleet equivalence grid (real worker processes)
# ---------------------------------------------------------------------------

N, M, ITERS = 14, 6, 5


@pytest.mark.parametrize("sim_gpus,expect", [
    ((), "np_only"),
    ((0, 1), "jnp_only"),
    ((1,), "mixed"),
])
def test_equivalence_grid_across_fleets(sim_gpus, expect):
    """The same compiled pfor on np-only, jnp-only and mixed clusters:
    identical results (atol 1e-8) and routing telemetry showing the
    expected backend mix actually executed chunks."""
    x, y, _ = _make_data(N, M)
    ref = _reference(x, y, N, M, ITERS)
    ck = compile_kernel(hetero_kernel)   # compile once, bind per fleet
    rt = ClusterRuntime(workers=2, sim_gpu_workers=sim_gpus)
    try:
        ck.pfor_config.runtime = rt
        ck.pfor_config.workers = 2
        ck.pfor_config.distribute_threshold = 0
        for _ in range(2):               # second call exercises blob reuse
            out = np.zeros(N)
            ck.call_variant("np", x, y, out, N, M, ITERS)
            assert np.allclose(out, ref, atol=1e-8)
        st = rt.stats()
        assert st["chunks_dispatched"] >= 4
        ran = st["chunks_executed"]     # confirmed by worker dones
        if expect == "np_only":
            assert st["gpu_chunks"] == 0 and st["cpu_chunks"] > 0
            assert set(ran) == {"np"}
        elif expect == "jnp_only":
            assert st["cpu_chunks"] == 0 and st["gpu_chunks"] > 0
            assert set(ran) == {"jnp"}
        else:
            assert st["gpu_chunks"] > 0 and st["cpu_chunks"] > 0
            assert ran.get("np", 0) > 0 and ran.get("jnp", 0) > 0
            (mix,) = st["unit_backend"].values()
            assert set(mix) == {"np", "jnp"}
        assert st["blob_hits"] > 0       # serving-loop reuse survives
    finally:
        rt.shutdown()
        ck.pfor_config.runtime = None


def test_env_pose_survives_respawn(monkeypatch):
    """A worker posing via REPRO_DISTRIB_SIM_GPU must keep the pose
    when respawned — the replacement's fresh wid no longer matches the
    env wid list, so the pose is resolved at spawn time and inherited
    (review finding)."""
    monkeypatch.setenv("REPRO_DISTRIB_SIM_GPU", "1")
    rt = ClusterRuntime(workers=2)
    try:
        assert [p.wid for p in rt.profiles() if p.has_gpu] == [1]
        assert rt.kill_worker(wid=1) is not None
        deadline = time.time() + 30.0
        while time.time() < deadline and rt.worker_deaths < 1:
            time.sleep(0.05)      # death not noticed yet
        while time.time() < deadline:
            profs = rt.profiles()
            if any(p.has_gpu and p.wid != 1 for p in profs):
                break
            time.sleep(0.05)
        profs = rt.profiles()
        assert any(p.has_gpu and p.wid != 1 for p in profs), \
            [(p.wid, p.has_gpu) for p in profs]
    finally:
        rt.shutdown()


def test_mixed_fleet_survives_worker_kill():
    """SIGKILL the GPU-posing worker mid-serving-loop: the respawn
    inherits the pose, chunks resubmit, results stay exact."""
    x, y, _ = _make_data(N, M)
    ref = _reference(x, y, N, M, ITERS)
    ck = compile_kernel(hetero_kernel)
    rt = ClusterRuntime(workers=2, sim_gpu_workers=(1,))
    try:
        ck.pfor_config.runtime = rt
        ck.pfor_config.workers = 2
        ck.pfor_config.distribute_threshold = 0
        for call in range(6):
            if call == 2:
                assert rt.kill_worker(wid=1) is not None
            out = np.zeros(N)
            ck.call_variant("np", x, y, out, N, M, ITERS)
            assert np.allclose(out, ref, atol=1e-8), f"call {call}"
        assert rt.worker_deaths == 1
        # the pose survived the respawn: jnp chunks kept flowing
        profs = rt.profiles()
        assert any(p.has_gpu for p in profs)
        assert rt.stats()["chunks_executed"].get("jnp", 0) > 0
    finally:
        rt.shutdown()
        ck.pfor_config.runtime = None


# ---------------------------------------------------------------------------
# accelerated hetero path: jitted twins, residency, row-skip, pipelining
# ---------------------------------------------------------------------------

def test_codegen_emits_jit_iteration_fast_path():
    """The jnp twin leads with a per-iteration function handed to
    ``__pfor_jit`` (vmap + jit + scatter); its eager loop stays as the
    fallback below the dispatch."""
    ck = compile_kernel(hetero_kernel)
    src = ck.source("np")
    assert "def __pfor_iter_0(" in src
    assert "if __pfor_jit(__pfor_iter_0, __lo, __hi" in src
    # the sequential convergence loop compiles to a fori_loop carry
    assert "__jax.lax.fori_loop(" in src
    assert ck.stats().get("pfor_jit_units") == 1


def test_jit_iter_matches_eager_twin_inprocess():
    """The vmapped compiled path and the eager twin loop produce the
    same rows; the second call hits the compiled-executable cache."""
    from repro.distrib import accel

    accel.reset()
    bodies = {}

    class FakeRT:
        def pfor_shards(self, body, lo, hi, tile, **kw):
            bodies["jnp"] = body.__jnp__
            body.__jnp__(lo, hi)

        def distribute_profitable(self, *a, **k):
            return True

    ck = compile_kernel(hetero_kernel, runtime=FakeRT())
    ck.pfor_config.distribute_threshold = 0
    x, y, _ = _make_data(N, M)
    ref = _reference(x, y, N, M, ITERS)
    try:
        out = np.zeros(N)
        ck.call_variant("np", x, y, out, N, M, ITERS)
        assert np.allclose(out, ref, atol=1e-8)
        st = accel.stats()
        assert st.get("jit_recompiles", 0) == 1
        assert st.get("jit_fallbacks", 0) == 0
        out2 = np.zeros(N)
        ck.call_variant("np", x, y, out2, N, M, ITERS)
        assert np.allclose(out2, ref, atol=1e-8)
        st = accel.stats()
        assert st.get("jit_recompiles", 0) == 1   # no new compilation
        assert st.get("jit_hits", 0) >= 1
    finally:
        accel.reset()


def test_jit_disabled_by_env_falls_back_to_eager(monkeypatch):
    from repro.distrib import accel

    accel.reset()
    monkeypatch.setenv("REPRO_DISTRIB_JIT", "0")

    class FakeRT:
        def pfor_shards(self, body, lo, hi, tile, **kw):
            body.__jnp__(lo, hi)

        def distribute_profitable(self, *a, **k):
            return True

    ck = compile_kernel(hetero_kernel, runtime=FakeRT())
    ck.pfor_config.distribute_threshold = 0
    x, y, _ = _make_data(N, M)
    ref = _reference(x, y, N, M, ITERS)
    try:
        out = np.zeros(N)
        ck.call_variant("np", x, y, out, N, M, ITERS)
        assert np.allclose(out, ref, atol=1e-8)
        st = accel.stats()
        assert st.get("jit_recompiles", 0) == 0
        assert st.get("jit_hits", 0) == 0
    finally:
        accel.reset()


def test_resident_arrays_skip_restaging():
    """remember()-ed arrays stage to the device once; later pfor_jit
    calls over the same buffers are residency hits, including through a
    fresh re-based chunk view of the same rows array."""
    from repro.distrib import accel
    from repro.distrib.serial import rebase_chunk

    accel.reset()
    rows = np.arange(12.0).reshape(4, 3)
    accel.remember(rows)

    def iter_fn(g, __offs, a):
        row = a[g - __offs[0]]
        return (row * 2.0,)

    out = rebase_chunk(rows.copy(), 0)
    try:
        assert accel.pfor_jit(iter_fn, 0, 4, (rebase_chunk(rows, 0),),
                              (0,)) is True
        st = accel.stats()
        first_stages = st.get("resident_stages", 0)
        assert st.get("resident_cells", 0) >= 1
        # a *new* view object over the same cached rows buffer must hit
        assert accel.pfor_jit(iter_fn, 0, 4, (rebase_chunk(rows, 0),),
                              (0,)) is True
        st = accel.stats()
        assert st.get("resident_hits", 0) >= 1
        assert st.get("resident_stages", 0) == first_stages
    finally:
        accel.reset()
    del out


def test_serving_loop_reaches_steady_state_telemetry():
    """Three serving-loop calls on a posed-GPU fleet: after the first,
    zero new XLA compilations, device residency hits, and chunk rows
    skipped (the head's content hash matched) — with exact results."""
    x, y, _ = _make_data(N, M)
    ref = _reference(x, y, N, M, ITERS)
    ck = compile_kernel(hetero_kernel)
    rt = ClusterRuntime(workers=2, sim_gpu_workers=(0, 1))
    try:
        ck.pfor_config.runtime = rt
        ck.pfor_config.workers = 2
        ck.pfor_config.distribute_threshold = 0
        seen = []
        for _ in range(3):
            out = np.zeros(N)
            ck.call_variant("np", x, y, out, N, M, ITERS)
            assert np.allclose(out, ref, atol=1e-8)
            seen.append(rt.stats())
        assert seen[0]["jit_recompiles"] > 0
        # steady state: the compiled executable is reused verbatim
        assert seen[2]["jit_recompiles"] == seen[0]["jit_recompiles"]
        assert seen[2]["jit_hits"] > seen[0]["jit_hits"]
        assert seen[2]["jit_fallbacks"] == 0
        # device residency: later calls reuse staged arrays
        assert seen[2]["resident_hits"] > seen[0]["resident_hits"]
        assert seen[2]["resident_stages"] == seen[0]["resident_stages"]
        # unchanged chunk rows ride the ("keep",) marker, not the wire
        assert seen[2]["rows_skipped"] > 0
        assert seen[2]["bytes_saved_rows"] > 0
    finally:
        rt.shutdown()
        ck.pfor_config.runtime = None


def test_pipelined_rounds_match_synchronous_bitwise():
    """pipeline_depth=2 (sub-chunked, as-completed gather) must produce
    bitwise-identical arrays to the depth-1 synchronous round — pfor
    chunks write disjoint regions, so merge order cannot matter.

    Both depths run on one fleet: the rows each worker (and so each
    backend) computes follow the measured profiles, and two fleets
    measure different rates, which would move rows between the np and
    jnp bodies (equal only to rounding)."""
    x, y, _ = _make_data(N, M)
    outs = {}
    ck = compile_kernel(hetero_kernel)
    rt = ClusterRuntime(workers=2, sim_gpu_workers=(1,))
    try:
        ck.pfor_config.runtime = rt
        ck.pfor_config.workers = 2
        ck.pfor_config.distribute_threshold = 0
        for depth in (1, 2):
            rt.pipeline_depth = depth
            before = rt.stats()["chunks_dispatched"]
            out = np.zeros(N)
            ck.call_variant("np", x, y, out, N, M, ITERS)
            outs[depth] = out
            st = rt.stats()
            assert st["pipeline_depth"] == depth
            if depth > 1:
                # each worker share split into `depth` sub-chunks
                assert st["chunks_dispatched"] - before >= 2 * 2
                assert "overlap_s" in rt.phase_breakdown()
    finally:
        rt.shutdown()
        ck.pfor_config.runtime = None
    assert np.array_equal(outs[1], outs[2]), \
        "pipelined gather diverged from synchronous round"


def test_np_only_knob_suppresses_twin_routing():
    """np_only=True is the control arm for speedup comparisons: same
    fleet, no jnp chunks, same results."""
    x, y, _ = _make_data(N, M)
    ref = _reference(x, y, N, M, ITERS)
    ck = compile_kernel(hetero_kernel)
    rt = ClusterRuntime(workers=2, sim_gpu_workers=(0, 1), np_only=True)
    try:
        ck.pfor_config.runtime = rt
        ck.pfor_config.workers = 2
        ck.pfor_config.distribute_threshold = 0
        out = np.zeros(N)
        ck.call_variant("np", x, y, out, N, M, ITERS)
        assert np.allclose(out, ref, atol=1e-8)
        st = rt.stats()
        assert st["gpu_chunks"] == 0 and st["cpu_chunks"] > 0
        assert set(st["chunks_executed"]) == {"np"}
    finally:
        rt.shutdown()
        ck.pfor_config.runtime = None


def test_gpu_probe_error_lands_on_profile(monkeypatch):
    """A failing device probe must report *why* instead of silently
    posing as a bare CPU (a chip-owning worker refuses its hello with
    the reason)."""
    def boom():
        raise RuntimeError("driver exploded")

    monkeypatch.setattr(jax, "devices", boom)
    p = measure_profile(0, sim_gpu=False, device=True)
    assert "driver exploded" in p.gpu_probe_error
    assert not p.has_gpu
    # the reason survives the hello-message dict roundtrip
    assert DeviceProfile.from_dict(
        p.as_dict()).gpu_probe_error == p.gpu_probe_error


# ---------------------------------------------------------------------------
# tracked flaky: recv/send racing a connection close
# ---------------------------------------------------------------------------

def test_handle_send_translates_closed_handle_typeerror():
    """mp.Connection.close() nulls its OS handle without a lock; a send
    racing it historically surfaced as ``TypeError: 'NoneType' object
    cannot be interpreted as an integer`` from a cluster-recv thread.
    The handle wrapper must turn that into the OSError every caller
    already handles."""

    class _RacyConn:
        def send(self, msg):
            raise TypeError(
                "'NoneType' object cannot be interpreted as an integer")

        def close(self):
            pass

    wh = _WorkerHandle(0, None, _RacyConn())
    with pytest.raises(OSError):
        wh.send(("ping", b""))


def test_handle_close_serializes_behind_sends():
    """Hammer send() from one thread while close_conn() lands from
    another: every failure must be OSError, never TypeError."""
    a, b = mp.Pipe()
    wh = _WorkerHandle(0, None, a)
    errors = []
    stop = threading.Event()

    def drain():       # keep the pipe from backpressure-blocking send()
        while not stop.is_set():
            try:
                if b.poll(0.01):
                    b.recv()
            except (EOFError, OSError):
                return

    def sender():
        for _ in range(2000):
            try:
                wh.send(("ping", b"x" * 4096))
            except OSError:
                return
            except BaseException as exc:  # noqa: BLE001
                errors.append(exc)
                return

    dr = threading.Thread(target=drain, daemon=True)
    dr.start()
    t = threading.Thread(target=sender)
    t.start()
    time.sleep(0.005)
    wh.close_conn()
    t.join(10.0)
    alive = t.is_alive()
    stop.set()
    b.close()
    assert not alive, "sender wedged behind close_conn"
    assert not errors, errors


def test_worker_sigkill_mid_handshake_no_unraisable():
    """SIGKILL workers right after (re)spawn — while the head is still
    mid-handshake (hello / reprofile / transport ping) — and assert no
    thread dies with an unhandled exception (the tracked flaky's
    signature) and the fleet still computes correctly afterwards."""
    seen = []
    prev_hook = threading.excepthook
    threading.excepthook = lambda args: seen.append(args)
    rt = ClusterRuntime(workers=2)
    try:
        for _ in range(4):
            rt.kill_worker()          # respawn starts a fresh handshake
            time.sleep(0.05)          # land the next kill inside it
        # wait for *profiled* workers (hello completed), not merely
        # alive handles — pfor placement only sees profiled views
        deadline = time.time() + 30.0
        while len(rt.profiles()) < 2 and time.time() < deadline:
            time.sleep(0.05)
        x, y, _ = _make_data(N, M)
        ref = _reference(x, y, N, M, ITERS)
        ck = compile_kernel(hetero_kernel, runtime=rt)
        ck.pfor_config.distribute_threshold = 0
        out = np.zeros(N)
        ck.call_variant("np", x, y, out, N, M, ITERS)
        assert np.allclose(out, ref, atol=1e-8)
    finally:
        rt.shutdown()
        threading.excepthook = prev_hook
    fatal = [s for s in seen if s.exc_type is not None]
    assert not fatal, [f"{s.exc_type.__name__}: {s.exc_value}"
                       for s in fatal]
