"""Pluggable backend registry: one object per code-variant target.

Until this module existed, the np/jnp twin pair was hand-woven through
codegen (twin emission), cost (a hard-coded ``jnp`` branch), serial
(backend tags), and the cluster (bodies dict / ``TaskSpec.alt``). A
:class:`Backend` now owns everything that made those layers
backend-aware:

  * its **module binding** — the namespace symbol the generated twin
    computes through (``__jxp`` → ``jax.numpy``, ``__plk`` → the
    pallas lowering surface) and the importable module behind it (which
    is also how the twin ships to workers: a module global rides the
    serializer's existing module-by-name marker);
  * its **dtype map** — how annotation dtypes land on the device;
  * its **pfor-body codegen idiom** — an ``emit_twin`` hook the emitter
    calls per accelerator-feasible pfor unit (returning None when the
    unit does not fit this backend's shape);
  * its **compile hook** — the exec-namespace bindings a generated
    variant needs (``accel.pfor_jit`` is the jnp backend's hook);
  * its **cost profile** — the gflops/membw/launch-overhead terms
    :func:`repro.core.cost.pick_chunk_backend` prices a (unit, backend,
    worker) cell with;
  * its **serialization tag** — the token the variant-cache key and the
    cluster's per-chunk blob tagging derive from.

``codegen.emit_pfor`` iterates :func:`twin_backends` instead of
hard-coding a pair; the cluster's degradation chain
(:func:`degradation_chain`) and the compiler's cache tag
(:func:`cache_token`) are registry-derived. Adding an accelerator —
the ``pallas`` backend below, or CuPy/Triton later — is one
:func:`register` call, not a cross-layer sweep.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import (Any, Callable, Collection, Dict, FrozenSet, List,
                    Optional)

__all__ = [
    "Backend", "BackendUnavailable", "register", "unregister", "get",
    "is_registered", "names", "twin_backends", "twin_names",
    "degradation_chain", "cache_token", "feasible", "on_chip",
    "device_precision",
]


class BackendUnavailable(RuntimeError):
    """A registered backend's runtime dependency is missing."""


# Default device dtype map (PolyBench float64 semantics preserved on
# accelerators via x64; integer index math stays 64-bit).
_NP_DTYPES = {"f32": "float32", "f64": "float64",
              "i32": "int32", "i64": "int64"}


@dataclass
class Backend:
    """One retargetable code-variant target (slope/Loo.py-style)."""

    name: str
    # namespace symbol the twin body computes through, and the module
    # imported behind it ("" for np: the base variant's own ``xp``)
    xp_binding: str = ""
    module: str = ""
    # serialization/cache token component; bumping it invalidates cached
    # variants generated with an older codegen idiom for this backend
    codegen_version: int = 1
    # placement preference for chunks routed to this backend in a
    # heterogeneous round ('' | 'cpu' | 'gpu')
    device_pref: str = "cpu"
    # routing preference order: ties and zero-flop estimates resolve to
    # the highest-priority feasible candidate; degradation walks down
    priority: int = 0
    # whether codegen emits a per-unit pfor twin body for this backend
    twin: bool = False
    dtype_map: Dict[str, str] = field(default_factory=lambda: dict(_NP_DTYPES))
    # (emitter, unit, body_name, idx, pending_syms) -> twin fn name | None
    emit_twin: Optional[Callable[..., Optional[str]]] = None
    # (emit_meta) -> exec-namespace bindings for variants whose meta
    # records twin units of this backend
    namespace: Optional[Callable[[Any], Dict[str, Any]]] = None
    # (flops, nbytes, profile) -> estimated seconds for one chunk
    chunk_seconds: Optional[Callable[[float, float, Any], float]] = None
    # (profile) -> chunk-sizing throughput weight
    effective_gflops: Optional[Callable[[Any], float]] = None
    # (profile) -> can this worker run the twin at all
    feasible: Optional[Callable[[Any], bool]] = None
    # array dtypes this backend's bodies run on a real accelerator chip
    # (None: any); see :func:`feasible`
    chip_dtypes: Optional[FrozenSet[str]] = None

    @property
    def attr(self) -> str:
        """Attribute name the np body carries this twin under."""
        return f"__{self.name}__"

    @property
    def tag(self) -> str:
        """Serialization/cache token component."""
        return f"{self.name}{self.codegen_version}"


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Backend] = {}


def register(backend: Backend) -> Backend:
    """Register (or replace) a backend. Registration order is the twin
    emission order; pricing/degradation order comes from ``priority``."""
    if backend.name == "np" and backend.twin:
        raise ValueError("the np base backend cannot be a twin")
    _REGISTRY[backend.name] = backend
    return backend


def unregister(name: str) -> Optional[Backend]:
    """Remove a backend (test isolation for toy registrations). The np
    base backend cannot be removed."""
    if name == "np":
        raise ValueError("cannot unregister the np base backend")
    return _REGISTRY.pop(name, None)


def get(name: str) -> Backend:
    return _REGISTRY[name]


def is_registered(name: str) -> bool:
    return name in _REGISTRY


def names() -> List[str]:
    return list(_REGISTRY)


def twin_backends() -> List[Backend]:
    """Twin-emitting backends in registration (= emission) order."""
    return [b for b in _REGISTRY.values() if b.twin]


def twin_names() -> List[str]:
    return [b.name for b in _REGISTRY.values() if b.twin]


def degradation_chain(name: str) -> List[str]:
    """Backends a failing chunk of ``name`` degrades through, ordered by
    descending priority and always ending at ``np`` — the
    ``TaskSpec.alt`` chain (pallas → jnp → np)."""
    start = _REGISTRY.get(name)
    pri = start.priority if start is not None else 0
    lower = sorted((b for b in _REGISTRY.values()
                    if b.twin and b.priority < pri and b.name != name),
                   key=lambda b: -b.priority)
    chain = [b.name for b in lower]
    if "np" not in chain and name != "np":
        chain.append("np")
    return chain


# Matmul precision the device bodies (whole-kernel jnp variants and
# every accelerator twin) compute at. The TPU's default runs an f32
# matmul as one bf16 pass, about three significant digits, which the np
# reference does not match; "highest" makes it f32-accurate. CPU
# backends compute at full precision either way.
DEVICE_MATMUL_PRECISION = "highest"


def device_precision():
    """Context in which device bodies are traced and run."""
    import jax

    return jax.default_matmul_precision(DEVICE_MATMUL_PRECISION)


def _dtypes_run_on(bk: Backend, platform: str,
                   dtypes: Collection[str]) -> bool:
    """Do ``bk``'s bodies run arrays of ``dtypes`` on ``platform`` (a
    profile's ``gpu_kind`` or a jax backend name)? Off a real chip (no
    device, jax's CPU, or jax-CPU posing as a device in tests) every
    dtype runs; on one only the backend's ``chip_dtypes``."""
    return (bk.chip_dtypes is None or platform in ("", "sim", "cpu")
            or set(dtypes) <= bk.chip_dtypes)


def feasible(bk: Backend, profile, dtypes: Collection[str] = ()) -> bool:
    """Can ``profile``'s worker run ``bk``'s body for a unit whose
    captured arrays hold ``dtypes``? On a real chip a dtype outside the
    backend's ``chip_dtypes`` keeps the unit off it: the TPU compiler
    aborts the whole process on a c128 matmul (no exception to catch),
    and Mosaic has no f64. Lowering those dtypes is a separate policy;
    here such units simply stay on np."""
    if bk.feasible is not None and not bk.feasible(profile):
        return False
    return _dtypes_run_on(bk, getattr(profile, "gpu_kind", ""), dtypes)


def runs_here(bk: Backend, dtypes: Collection[str] = ()) -> bool:
    """Can this process run ``bk``'s body itself (the in-process
    whole-kernel variant) on arrays of ``dtypes``? The dtype rule of
    :func:`feasible`, applied to the platform jax gives this process."""
    import jax

    return _dtypes_run_on(bk, jax.default_backend(), dtypes)


def cache_token(accel_ok: bool) -> str:
    """Registry-derived variant-cache token: sorted backend names, each
    with its codegen version. Twin backends are earned only when the
    accelerator runtime is actually importable (``accel_ok``), so a
    jax-less host files twin-less variants under the np-only token and
    recompiles with twins once jax appears. Distinct by construction
    from the pre-registry "np+jnpu" / "np+jnp" literals, so old cache
    entries miss into a recompile instead of serving stale code."""
    active = [b for b in _REGISTRY.values() if accel_ok or not b.twin]
    return "+".join(b.tag for b in sorted(active, key=lambda b: b.name))


# ---------------------------------------------------------------------------
# Cost-profile terms (imported by repro.core.cost; kept here so a
# backend's pricing rides its registration)
# ---------------------------------------------------------------------------

# Per-chunk accelerator launch overhead for the jnp twin (host→device
# staging + XLA dispatch); conservative so tiny chunks stay on np.
GPU_CHUNK_OVERHEAD_S = 5e-3

# Host↔device staging bandwidth fallback when the profile carries no
# measured number (PCIe-gen3-ish, GB/s).
GPU_XFER_GBS = 12.0

# Fused-kernel advantage of the pallas backend over the generic jnp op
# stream: tiled MXU-style compute and operands touched once instead of
# per-op re-materialization. Both the compute and transfer roofline
# terms improve by this factor, so a matched unit routes to pallas only
# where its arithmetic-intensity win is real — on a real device the
# (smaller) kernel-launch overhead still prices tiny chunks back to
# np/jnp.
PALLAS_FUSION_SPEEDUP = 1.6

# Per-chunk pallas kernel launch overhead on a real device (a compiled
# pallas_call dispatch is cheaper than a full XLA op-stream round).
PALLAS_CHUNK_OVERHEAD_S = 2e-3


def _np_chunk_seconds(flops: float, nbytes: float, profile) -> float:
    rate = max(1e-3, getattr(profile, "gflops", 1.0))
    membw = max(1e-3, getattr(profile, "membw_gbs", 1.0))
    return max(flops / (rate * 1e9), nbytes / (membw * 1e9))


def _gpu_xfer_overhead(profile) -> tuple:
    """(xfer_gbs, real_device) staging terms shared by the accelerator
    backends. A *simulated* GPU (jax-CPU posing for laptops/CI) prices
    like an integrated accelerator — no staging overhead, memory
    bandwidth as the transfer term; real devices use the bandwidth the
    device probe measured, falling back to the PCIe-ish constant."""
    if getattr(profile, "gpu_kind", "") == "sim":
        return max(1e-3, getattr(profile, "membw_gbs", 1.0)), False
    h2d = getattr(profile, "h2d_gbs", 0.0) or 0.0
    d2h = getattr(profile, "d2h_gbs", 0.0) or 0.0
    measured = (min(b for b in (h2d, d2h) if b > 0)
                if (h2d > 0 or d2h > 0) else 0.0)
    return (measured if measured > 0 else GPU_XFER_GBS), True


def _jnp_chunk_seconds(flops: float, nbytes: float, profile) -> float:
    rate = max(1e-3, getattr(profile, "gpu_gflops", 0.0))
    xfer_gbs, real = _gpu_xfer_overhead(profile)
    overhead = GPU_CHUNK_OVERHEAD_S if real else 0.0
    return max(flops / (rate * 1e9),
               nbytes / (xfer_gbs * 1e9)) + overhead


def _pallas_chunk_seconds(flops: float, nbytes: float, profile) -> float:
    rate = max(1e-3, getattr(profile, "gpu_gflops", 0.0)) \
        * PALLAS_FUSION_SPEEDUP
    xfer_gbs, real = _gpu_xfer_overhead(profile)
    xfer_gbs *= PALLAS_FUSION_SPEEDUP
    overhead = PALLAS_CHUNK_OVERHEAD_S if real else 0.0
    return max(flops / (rate * 1e9),
               nbytes / (xfer_gbs * 1e9)) + overhead


# every dtype XLA runs on the chip except complex128 (f64 is emulated:
# slow, but correct)
_JNP_CHIP_DTYPES = frozenset({
    "bool", "int8", "int16", "int32", "int64", "uint8", "uint16",
    "uint32", "uint64", "float16", "bfloat16", "float32", "float64",
    "complex64"})

# the in-repo Pallas kernels compile for f32 and bf16 operands
_PALLAS_CHIP_DTYPES = frozenset({"float32", "bfloat16"})


def _accel_feasible(profile) -> bool:
    return (getattr(profile, "has_gpu", False)
            and getattr(profile, "gpu_gflops", 0.0) > 0)


def _gpu_effective_gflops(profile) -> float:
    return max(1e-3, getattr(profile, "gpu_gflops", 0.0))


def _np_effective_gflops(profile) -> float:
    return max(1e-3, getattr(profile, "gflops", 1.0))


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------

def _jnp_emit_twin(emitter, u, body_name: str, idx: int,
                   pending_syms) -> Optional[str]:
    return emitter._try_emit_jnp_twin(u, body_name, idx, pending_syms)


def _jnp_namespace(meta) -> Dict[str, Any]:
    """Exec bindings for variants with jnp twin units: jax.numpy under
    ``__jxp``, plus the ``__pfor_jit`` compile hook (vmap/jit/residency,
    :func:`repro.distrib.accel.pfor_jit`) for units that also carry the
    jit-iteration fast path."""
    try:
        import jax

        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
    except Exception as exc:
        raise BackendUnavailable(
            f"hybrid np variant references jax, which is unavailable: "
            f"{exc}")
    ns: Dict[str, Any] = {"__jxp": jnp}
    if getattr(meta, "pfor_jit_units", None):
        from repro.distrib.accel import pfor_jit

        ns["__jax"] = jax
        ns["__pfor_jit"] = pfor_jit
    return ns


def _pallas_emit_twin(emitter, u, body_name: str, idx: int,
                      pending_syms) -> Optional[str]:
    from .patterns import match_pfor_unit

    m = match_pfor_unit(u)
    if m is None:
        return None
    name = f"{body_name}__pallas"
    emitter.w(f"def {name}(__lo, __hi):")
    emitter.depth += 1
    for line in m.body_lines:
        emitter.w(line)
    emitter.depth -= 1
    return name


def _pallas_namespace(meta) -> Dict[str, Any]:
    try:
        import repro.kernels.api as _plk
    except Exception as exc:
        raise BackendUnavailable(
            f"pallas twin references repro.kernels.api, which failed "
            f"to import: {exc}")
    return {"__plk": _plk}


register(Backend(
    name="np",
    codegen_version=1,
    device_pref="cpu",
    priority=10,
    twin=False,
    chunk_seconds=_np_chunk_seconds,
    effective_gflops=_np_effective_gflops,
    feasible=lambda profile: True,
))

register(Backend(
    name="jnp",
    xp_binding="__jxp",
    module="jax.numpy",
    codegen_version=1,
    device_pref="gpu",
    priority=20,
    twin=True,
    emit_twin=_jnp_emit_twin,
    namespace=_jnp_namespace,
    chunk_seconds=_jnp_chunk_seconds,
    effective_gflops=_gpu_effective_gflops,
    feasible=_accel_feasible,
    chip_dtypes=_JNP_CHIP_DTYPES,
))

register(Backend(
    name="pallas",
    xp_binding="__plk",
    module="repro.kernels.api",
    codegen_version=1,
    device_pref="gpu",
    priority=30,
    twin=True,
    emit_twin=_pallas_emit_twin,
    namespace=_pallas_namespace,
    chunk_seconds=_pallas_chunk_seconds,
    effective_gflops=lambda p: _gpu_effective_gflops(p)
    * PALLAS_FUSION_SPEEDUP,
    feasible=_accel_feasible,
    chip_dtypes=_PALLAS_CHIP_DTYPES,
))
