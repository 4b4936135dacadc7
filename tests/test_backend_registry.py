"""Backend-registry contract: registration is the whole integration.

A backend registered through :mod:`repro.core.backends` must flow
through every layer with **no edits outside the registration site**:
codegen emits its twin, the compiler binds its namespace hook, cost
prices it, the cluster routes chunks to it, and ``TaskSpec.alt``
degrades away from it when its chunks fail. The toy backend here is an
np-clone (same emitted loop, spy-instrumented compile hook); the boom
backend emits a twin that always raises, proving the degradation chain.

Also covers the registry-derived variant-cache key (satellite: entries
written by the pre-registry compiler under the literal ``np+jnpu`` tag
must still load without crashing and miss into a recompile).
"""

import os
import pickle

import numpy as np
import pytest

import jax  # noqa: F401  (worker forks inherit the loaded module)

from repro.core import backends, codegen, cost
from repro.core.compiler import _rebuild_from_entry, compile_kernel
from repro.core.pfor import PforConfig
from repro.distrib import ClusterRuntime, DeviceProfile
from repro.profiler.cache import VariantCache


def reg_kernel(A: "ndarray[f64,2]", out: "ndarray[f64,1]",
               n: int, m: int):
    for i in range(0, n):
        w = 2.0 * A[i, 0:m]
        out[i] = np.dot(w[0:m], A[i, 0:m])


def _reference(A, n, m):
    out = np.zeros(n)
    reg_kernel(A, out, n, m)
    return out


# ---------------------------------------------------------------------------
# registry unit surface
# ---------------------------------------------------------------------------

def test_builtin_registry_shape():
    assert {"np", "jnp", "pallas"} <= set(backends.names())
    assert not backends.get("np").twin
    # registration order is the twin emission order (jnp first keeps
    # pre-registry generated sources byte-stable)
    tw = backends.twin_names()
    assert tw.index("jnp") < tw.index("pallas")
    assert backends.get("pallas").attr == "__pallas__"
    assert backends.get("jnp").tag == "jnp1"


def test_degradation_chains():
    assert backends.degradation_chain("pallas") == ["jnp", "np"]
    assert backends.degradation_chain("jnp") == ["np"]
    assert backends.degradation_chain("np") == []


def test_cache_token_is_registry_derived():
    tok = backends.cache_token(True)
    assert tok == "jnp1+np1+pallas1"
    assert backends.cache_token(False) == "np1"
    # distinct by construction from every pre-registry literal
    assert tok not in ("np+jnpu", "np+jnp", "np")


def test_np_base_backend_is_protected():
    with pytest.raises(ValueError):
        backends.unregister("np")
    with pytest.raises(ValueError):
        backends.register(backends.Backend(name="np", twin=True))


# ---------------------------------------------------------------------------
# toy backend: an np-clone registered by tests only
# ---------------------------------------------------------------------------

def _clone_emit(suffix):
    """emit_twin hook producing an np-clone twin (the same eager loop
    the np body runs, emitted into a separate function scope)."""

    def emit(emitter, u, body_name, idx, pending_syms):
        name = f"{body_name}__{suffix}"
        sub = codegen.Emitter(emitter.s, "np")
        sub.depth = emitter.depth + 1
        sub.bound = set(emitter.bound)
        sub.pending_syms = {k: list(v) for k, v in pending_syms.items()}
        try:
            sub._emit_pfor_loop(u)
        except codegen.EmitError:
            return None
        emitter.w(f"def {name}(__lo, __hi):")
        emitter.depth += 1
        emitter.lines.extend(sub.lines)
        emitter.depth -= 1
        return name

    return emit


def _boom_emit(emitter, u, body_name, idx, pending_syms):
    name = f"{body_name}__boom"
    emitter.w(f"def {name}(__lo, __hi):")
    emitter.depth += 1
    emitter.w("raise RuntimeError('boom-backend')")
    emitter.depth -= 1
    return name


@pytest.fixture
def toy_backend():
    ns_calls = []

    def spy_namespace(meta):
        ns_calls.append(getattr(meta, "pfor_twin_units", None))
        return {"__toy": np}

    bk = backends.register(backends.Backend(
        name="toy", codegen_version=1, device_pref="cpu", priority=40,
        twin=True, emit_twin=_clone_emit("toy"), namespace=spy_namespace,
        chunk_seconds=lambda flops, nbytes, profile: 1e-9,
        effective_gflops=lambda profile: 1e6,
        feasible=lambda profile: True,
    ))
    bk.ns_calls = ns_calls
    try:
        yield bk
    finally:
        backends.unregister("toy")


@pytest.fixture
def boom_backend():
    backends.register(backends.Backend(
        name="boom", codegen_version=1, device_pref="cpu", priority=50,
        twin=True, emit_twin=_boom_emit,
        chunk_seconds=lambda flops, nbytes, profile: 1e-9,
        effective_gflops=lambda profile: 1e6,
        feasible=lambda profile: True,
    ))
    try:
        yield
    finally:
        backends.unregister("boom")


def test_toy_registration_reshapes_registry(toy_backend):
    assert backends.is_registered("toy")
    assert "toy" in backends.twin_names()
    # the cache token re-keys: old entries miss, new entries are distinct
    assert "toy1" in backends.cache_token(True)
    # degradation from toy walks the lower-priority twins down to np
    assert backends.degradation_chain("toy") == ["pallas", "jnp", "np"]
    # an unknown name degrades conservatively: straight to np
    assert backends.degradation_chain("boomless") == ["np"]


def test_toy_twin_emitted_and_priced(toy_backend):
    ck = compile_kernel(reg_kernel)
    src = ck.source("np")
    assert "def __pfor_body_0__toy(" in src
    assert "__pfor_body_0.__toy__ = __pfor_body_0__toy" in src
    assert "__pfor_body_0__toy.__backend__ = 'toy'" in src
    assert ck.pfor_twin_units().get("toy") == [0]
    # the spy compile hook ran while the variant was being bound
    assert toy_backend.ns_calls
    # cost prices the toy cell cheapest on any profile
    prof = DeviceProfile(wid=0, gflops=50.0, membw_gbs=10.0)
    assert cost.pick_chunk_backend(
        1e9, 1e6, prof, candidates=("toy",)) == "toy"
    assert cost.pick_chunk_backend(
        1e9, 1e6, prof, candidates=("toy", "jnp")) == "toy"
    assert cost.backend_effective_gflops(prof, "toy") == 1e6


def test_cluster_routes_chunks_to_toy(toy_backend):
    """End-to-end: register → codegen → serialization → worker
    execution, with routing telemetry confirming the toy backend ran."""
    rng = np.random.default_rng(5)
    n, m = 14, 6
    A = rng.normal(size=(n, m))
    ref = _reference(A, n, m)
    ck = compile_kernel(reg_kernel)
    rt = ClusterRuntime(workers=2)
    try:
        ck.pfor_config.runtime = rt
        ck.pfor_config.workers = 2
        ck.pfor_config.distribute_threshold = 0
        out = np.zeros(n)
        ck.call_variant("np", A, out, n, m)
        assert np.allclose(out, ref, atol=1e-8)
        st = rt.stats()
        assert st["chunks_executed"].get("toy", 0) > 0
        (mix,) = st["unit_backend"].values()
        assert set(mix) == {"toy"}
    finally:
        rt.shutdown()
        ck.pfor_config.runtime = None


def test_broken_backend_degrades_down_alt_chain(boom_backend):
    """A backend whose chunks always raise must degrade chunk-by-chunk
    down ``TaskSpec.alt`` (boom → np here: the fleet runs no jnp twin)
    and still produce correct results — counted, not crashed."""
    rng = np.random.default_rng(6)
    n, m = 14, 6
    A = rng.normal(size=(n, m))
    ref = _reference(A, n, m)
    ck = compile_kernel(reg_kernel)
    assert "def __pfor_body_0__boom(" in ck.source("np")
    rt = ClusterRuntime(workers=2)
    try:
        ck.pfor_config.runtime = rt
        ck.pfor_config.workers = 2
        ck.pfor_config.distribute_threshold = 0
        out = np.zeros(n)
        ck.call_variant("np", A, out, n, m)
        assert np.allclose(out, ref, atol=1e-8)
        ran = rt.stats()["chunks_executed"]
        assert ran.get("boom", 0) == 0
        # no worker of this CPU fleet can run the jnp twin: the chain
        # skips it and degrades straight to np
        assert ran.get("jnp", 0) == 0
        assert sum(ran.values()) > 0     # degraded chunks completed
    finally:
        rt.shutdown()
        ck.pfor_config.runtime = None


# ---------------------------------------------------------------------------
# variant-cache key regression (pre-registry "np+jnpu" entries)
# ---------------------------------------------------------------------------

def test_cache_roundtrip_under_registry_tag(tmp_path):
    cachedir = str(tmp_path / "vc")
    compile_kernel(reg_kernel, cache=cachedir)
    vc = VariantCache(cachedir)
    assert len(vc.entries()) == 1
    ck2 = compile_kernel(reg_kernel, cache=cachedir)
    assert getattr(ck2, "from_cache", False)


def test_legacy_np_jnpu_entry_loads_and_misses(tmp_path):
    """An entry written by the pre-registry compiler (literal
    ``np+jnpu`` tag, jnp-only twin metadata) must (a) rebuild without
    crashing through the legacy ``pfor_jnp_units`` projection and (b)
    never satisfy a registry-keyed lookup — it misses into a fresh
    compile instead of serving stale twin code."""
    cachedir = str(tmp_path / "vc")
    compile_kernel(reg_kernel, cache=cachedir)
    vc = VariantCache(cachedir)
    (key,) = vc.entries()
    path = os.path.join(cachedir, f"{key}.pkl")
    with open(path, "rb") as f:
        entry = pickle.load(f)

    # rewind the entry to its pre-registry shape: literal backend tag,
    # no per-backend twin-unit metadata
    entry.backend = "np+jnpu:dist:fuse"
    for gen in entry.generated.values():
        gen.meta.__dict__.pop("pfor_twin_units", None)
    os.unlink(path)
    vc.put(entry)
    assert len(vc.entries()) == 1

    # (a) the legacy entry still rebuilds (jnp-units projection)
    cfg = PforConfig(runtime=None, tile=None, workers=2)
    ck = _rebuild_from_entry(reg_kernel, entry, cfg,
                             cost.ACCEL_FLOP_THRESHOLD)
    assert ck is not None
    rng = np.random.default_rng(7)
    A = rng.normal(size=(9, 4))
    out = np.zeros(9)
    ck.call_variant("np", A, out, 9, 4)
    assert np.allclose(out, _reference(A, 9, 4), atol=1e-8)

    # (b) a registry-keyed compile misses the legacy entry and refiles
    vc2 = VariantCache(cachedir)
    ck2 = compile_kernel(reg_kernel, cache=vc2)
    assert not getattr(ck2, "from_cache", False)
    assert vc2.stats.misses == 1
    assert vc2.stats.codegen_skipped == 0
    assert len(vc2.entries()) == 2       # legacy + fresh registry entry
