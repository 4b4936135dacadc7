"""The rules that keep the device path on the chip, checked on the CPU:
who owns a chip, when Pallas kernels are interpreted, where compiled
executables are cached, and which dtypes may reach the device."""

import multiprocessing as mp
import threading
import time

import pytest

import jax

from repro.core import backends, cost, jaxcache
from repro.distrib import ClusterRuntime, DeviceProfile
from repro.kernels import api, interpret_mode


# ---------------------------------------------------------------------------
# chip ownership
# ---------------------------------------------------------------------------

def test_chip_owner_without_a_chip_fails_its_hello(monkeypatch):
    """A worker the head assigns a chip to, in a process held to the
    CPU platform, refuses its hello with the reason; the runtime raises
    it instead of running a fleet without its device."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    before = set(mp.active_children())
    with pytest.raises(RuntimeError,
                       match="assigned an accelerator chip.*no accelerator"):
        ClusterRuntime(workers=1, device_workers=1, hello_timeout_s=120)
    deadline = time.monotonic() + 10
    while set(mp.active_children()) - before and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not set(mp.active_children()) - before, "worker left running"


def test_chip_owner_whose_reprofile_fails_leaves(monkeypatch):
    """The re-measure the head asks for at start-up and after each
    respawn holds a chip owner to the same rule as its first hello: a
    probe that fails there is a refused hello, not a CPU profile."""
    from repro.distrib import worker

    calls = []

    def fake_profile(wid, sim_gpu=None, device=False):
        calls.append(device)
        err = "" if len(calls) == 1 else "RuntimeError: chip lost"
        return DeviceProfile(wid=wid, has_gpu=not err,
                             gpu_kind="" if err else "tpu",
                             gpu_gflops=0.0 if err else 1.0,
                             gpu_probe_error=err)

    monkeypatch.setattr(worker, "measure_profile", fake_profile)
    monkeypatch.setattr(jaxcache, "enable_compile_cache", lambda: "")
    head, child = mp.Pipe()
    t = threading.Thread(target=worker.worker_main,
                         args=(child, 0, False, 0.0, {}), daemon=True)
    t.start()
    try:
        assert head.poll(30) and head.recv()[0] == "hello"
        head.send(("profile",))
        assert head.poll(30)
        kind, reason = head.recv()
        assert kind == "hello_failed"
        assert "assigned an accelerator chip" in reason
        assert "chip lost" in reason
        t.join(10)
        assert not t.is_alive(), "the worker carried on without its chip"
        assert calls == [True, True]
    finally:
        head.close()


def test_head_raises_and_counts_a_failed_reprofile():
    """The head keeps a refused re-profile's reason, counts it as a
    fault, and a start-up re-profile that fails raises it."""
    rt = ClusterRuntime(workers=1)
    try:
        wh = rt._handles[0]
        real_send = wh.send

        def send(msg):
            if msg != ("profile",):
                return real_send(msg)
            rt._handle(wh, ("hello_failed", "worker 0 lost its chip"))

        wh.send = send
        with pytest.raises(RuntimeError, match="lost its chip"):
            rt._reprofile_sequentially()
        assert rt.stats()["faults"]["hello_failures"] == 1
    finally:
        rt.shutdown()


def test_cpu_only_fleet_keeps_fork():
    """The start method follows the chip assignment: with no device
    owner (and no sim-GPU poser) the fleet forks, and its workers hold
    no device."""
    rt = ClusterRuntime(workers=1)
    try:
        assert rt.device_workers == 0
        assert rt.start_method == "fork"
        assert not rt.profiles()[0].has_gpu
    finally:
        rt.shutdown()


# ---------------------------------------------------------------------------
# interpret mode
# ---------------------------------------------------------------------------

def test_interpret_mode_follows_the_process_backend(monkeypatch):
    assert jax.default_backend() == "cpu"
    assert interpret_mode()
    # no environment variable takes part in the decision
    monkeypatch.setenv("REPRO_DISTRIB_SIM_GPU", "all")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert interpret_mode()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not interpret_mode()
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert interpret_mode()   # the kernels are written for the TPU


def test_api_counts_interpreted_calls_on_cpu(monkeypatch):
    import numpy as np

    monkeypatch.setenv("REPRO_DISTRIB_SIM_GPU", "all")
    api.reset()
    a = np.ones((8, 8), np.float32)
    np.testing.assert_allclose(api.matmul(a, a), a @ a)
    s = api.take_stats()
    assert s["pallas_calls"] == 1
    assert s["pallas_interpret_calls"] == 1


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_config():
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_compile_cache_honours_the_environment(monkeypatch, cache_config,
                                               tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert jaxcache.enable_compile_cache() == str(tmp_path)
    # jax reads the variable itself: no other directory is set
    assert jax.config.jax_compilation_cache_dir is None
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_compile_cache_defaults_to_a_fixed_checkout_path(monkeypatch,
                                                          cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = jaxcache.enable_compile_cache()
    assert path == str(jaxcache.CHECKOUT / ".jax_cache")
    assert (jaxcache.CHECKOUT / "pyproject.toml").is_file()
    assert jax.config.jax_compilation_cache_dir == path
    assert jaxcache.enable_compile_cache() == path   # stable across calls


# ---------------------------------------------------------------------------
# dtypes the chip cannot run
# ---------------------------------------------------------------------------

def _prof(kind: str) -> DeviceProfile:
    return DeviceProfile(wid=0, gflops=50.0, membw_gbs=10.0, has_gpu=True,
                         gpu_gflops=20000.0, gpu_kind=kind,
                         h2d_gbs=5.0, d2h_gbs=5.0)


@pytest.mark.parametrize("dtypes,want", [
    (("float32",), "pallas"),
    (("float32", "float64"), "jnp"),      # Mosaic has no f64
    (("complex128",), "np"),              # aborts the TPU compiler
    (("complex128", "float64"), "np"),
], ids=["f32", "f64", "c128", "c128+f64"])
def test_chip_dtypes_gate_device_twins(dtypes, want):
    both = ("jnp", "pallas")
    tpu = _prof("tpu")
    assert cost.pick_chunk_backend(5e9, 1e6, tpu, candidates=both,
                                   dtypes=dtypes) == want
    # the CPU-backed test fake runs every dtype (interpret mode)
    assert cost.pick_chunk_backend(5e9, 1e6, _prof("sim"),
                                   candidates=both,
                                   dtypes=dtypes) == "pallas"


def test_c128_never_offered_to_a_device_twin():
    tpu = _prof("tpu")
    for name in backends.twin_names():
        assert not backends.feasible(backends.get(name), tpu,
                                     ("complex128",))
    assert backends.feasible(backends.get("np"), tpu, ("complex128",))


def test_head_that_holds_a_chip_cannot_hand_it_over(monkeypatch):
    from jax._src import xla_bridge

    from repro.distrib.device import keep_off_chips

    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="already holds a tpu device"):
        keep_off_chips()


def test_whole_kernel_jnp_variant_skips_c128_on_a_tpu(monkeypatch):
    import numpy as np

    from repro.core.multiversion import CompiledKernel

    c128 = {"x": np.zeros(4, np.complex128), "n": 4}
    f32 = {"x": np.zeros(4, np.float32), "n": 4}
    assert CompiledKernel._jnp_runs_here(c128)     # the CPU runs it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not CompiledKernel._jnp_runs_here(c128)
    assert CompiledKernel._jnp_runs_here(f32)
