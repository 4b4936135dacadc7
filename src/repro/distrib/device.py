"""Per-worker device profiles: measured capability, not configured.

Every worker process measures its own hardware at startup — a small
matmul for FLOP rate, a copy sweep for memory bandwidth, ``os`` probes
for core count and memory — and reports the profile in its hello
message. The head adds a measured transport bandwidth (payload ping over
the worker's pipe). The placement scheduler and the local-vs-distributed
profitability test in :mod:`repro.core.cost` consume these numbers; on a
heterogeneous fleet the pfor sharder sizes chunks proportional to
``gflops``.

Which worker owns an accelerator chip is the head's decision, made when
it spawns the worker (``ClusterRuntime(device_workers=...)``); a chip
belongs to one process at a time. :func:`pin_process` applies that
decision before jax loads a backend: every other worker is pinned to the
CPU platform and never probes a device. An owning worker probes its chip
in the dtype the chunk bodies run (f32) and reports ``device_kind``; one
that finds no accelerator records why in ``gpu_probe_error``, and the
worker refuses its hello with that reason instead of posing as a CPU.

For laptops/CI, ``REPRO_DISTRIB_SIM_GPU`` makes jax-CPU workers *pose*
as GPU workers so heterogeneous routing is exercisable anywhere:
``all``/``*`` marks every worker, a comma-separated wid list (e.g.
``1`` or ``0,2``) marks just those. A simulated GPU reports
``has_gpu=True``, ``gpu_kind="sim"`` and ``gpu_gflops = gflops ×
REPRO_DISTRIB_SIM_GPU_FACTOR`` (default 4) — routing and chunk sizing
behave exactly as with real hardware, the jnp bodies just execute on
the jax CPU backend.
"""

from __future__ import annotations

import os
import socket
import sys
import time
from dataclasses import asdict, dataclass
from typing import Any, Dict, Optional

import numpy as np


@dataclass
class DeviceProfile:
    wid: int
    host: str = ""
    pid: int = 0
    cpus: int = 1
    mem_bytes: int = 0
    gflops: float = 1.0            # measured matmul rate
    membw_gbs: float = 1.0         # measured copy bandwidth
    has_gpu: bool = False
    gpu_kind: str = ""             # platform: "tpu" / "gpu" / "sim" / ""
    device_kind: str = ""          # jax device_kind, e.g. "TPU v5 lite"
    visible_chips: str = ""        # the chip assignment this worker got
    device_files: str = ""         # accelerator device files it holds open
    gpu_gflops: float = 0.0        # measured (or simulated) device rate
    transport_mbs: float = 0.0     # filled by the head's payload ping
    h2d_gbs: float = 0.0           # measured host→device staging bandwidth
    d2h_gbs: float = 0.0           # measured device→host gather bandwidth
    gpu_probe_error: str = ""      # why the device probe failed

    def as_dict(self) -> Dict[str, Any]:
        return asdict(self)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "DeviceProfile":
        return DeviceProfile(**d)


def _probe_mem_bytes() -> int:
    try:
        return (os.sysconf("SC_PAGE_SIZE")
                * os.sysconf("SC_PHYS_PAGES"))
    except (ValueError, OSError, AttributeError):
        return 0


def chip_env(chip: int, port: int) -> Dict[str, str]:
    """TPU runtime settings that give one process exactly one chip of a
    multi-chip host: the chip's index, a one-chip process grid (a subset
    of the host's chips, which is what lets the runtime load in several
    processes side by side), and a port of its own for the runtime's
    process service."""
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(port),
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}"}


def pin_process(device_env: Optional[Dict[str, str]]) -> None:
    """Fix this process's jax platform before jax loads a backend.

    ``None`` pins the CPU platform. A dict (possibly empty) makes this
    process the owner of one accelerator chip and applies the
    chip-visibility settings it carries (:func:`chip_env`); the platform
    itself is left to jax, so an owner that finds no chip says so."""
    if device_env is None:
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax = sys.modules.get("jax")
        if jax is not None:
            jax.config.update("jax_platforms", "cpu")
        return
    os.environ.update(device_env)


def keep_off_chips() -> None:
    """Pin this process, a head whose workers own the chips, to jax's
    CPU platform. Only this process is affected (workers are pinned by
    :func:`pin_process`). A process that already holds an accelerator
    cannot hand it over, so that is an error, not a silent downgrade."""
    import jax
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "cpu":
        raise RuntimeError(
            f"this process already holds a {jax.default_backend()} "
            f"device; create the ClusterRuntime that owns device workers "
            f"before running anything on jax here")


def _held_device_files() -> str:
    """The accelerator device files this process holds open, comma
    separated (Linux; empty elsewhere). A process shown one chip of a
    multi-chip host sees it as chip 0 of a 1x1x1 topology, so these are
    what tell several owners' chips apart."""
    held = set()
    try:
        fds = os.listdir("/proc/self/fd")
    except OSError:
        return ""
    for fd in fds:
        try:
            path = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        # /dev/vfio/vfio is the shared container, not a chip
        if (path.startswith(("/dev/accel", "/dev/vfio/"))
                and path != "/dev/vfio/vfio"):
            held.add(path)
    return ",".join(sorted(held))


def _probe_device() -> tuple:
    """(platform, kind, device_files, gflops, h2d_gbs, d2h_gbs, error)
    of the accelerator this process owns, measured on the device.

    The timing matmul runs in f32, the dtype the device bodies compute
    in. A process that finds no accelerator returns the reason as the
    error string, never a bare CPU profile."""
    try:
        import jax
        import jax.numpy as jnp

        devs = jax.devices()
        if devs[0].platform == "cpu":
            return ("", "", "", 0.0, 0.0, 0.0,
                    f"no accelerator visible: jax found only "
                    f"{sorted({d.platform for d in devs})} (JAX_PLATFORMS="
                    f"{os.environ.get('JAX_PLATFORMS', '')!r})")
        dev = devs[0]
        n = 1024
        a = jnp.ones((n, n), dtype=jnp.float32)
        (a @ a).block_until_ready()   # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            (a @ a).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        gflops = 2.0 * n ** 3 / max(1e-9, best) / 1e9

        # staging bandwidth, both directions — what the chunk pricing in
        # core.cost actually spends per chunk (8 MB, the blob-cache
        # sweep size, so the number reflects bulk transfers)
        host = np.ones(1 << 21, dtype=np.float32)  # 8 MB
        jax.device_put(host).block_until_ready()
        h2d = d2h = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            on_dev = jax.device_put(host).block_until_ready()
            h2d = min(h2d, time.perf_counter() - t0)
            # a fresh array each time: jax keeps the host copy of an
            # array it has already transferred
            t0 = time.perf_counter()
            np.asarray(on_dev)
            d2h = min(d2h, time.perf_counter() - t0)
        h2d_gbs = host.nbytes / max(1e-9, h2d) / 1e9
        d2h_gbs = host.nbytes / max(1e-9, d2h) / 1e9
        return (dev.platform, dev.device_kind, _held_device_files(),
                round(gflops, 3), round(h2d_gbs, 3), round(d2h_gbs, 3), "")
    except Exception as exc:
        return ("", "", "", 0.0, 0.0, 0.0, f"{type(exc).__name__}: {exc}")


def sim_gpu_for(wid: int) -> bool:
    """Does ``REPRO_DISTRIB_SIM_GPU`` mark this wid as a posing GPU?"""
    env = os.environ.get("REPRO_DISTRIB_SIM_GPU", "").strip()
    if not env:
        return False
    if env in ("all", "*"):
        return wid >= 0
    try:
        return wid in {int(x) for x in env.split(",") if x.strip()}
    except ValueError:
        return False


def measure_profile(wid: int, n: int = 128, sim_gpu: bool = None,
                    device: bool = False) -> DeviceProfile:
    """Micro-benchmark this process. ``n`` keeps the probe ~milliseconds.
    ``sim_gpu`` forces the simulated-GPU pose (None = consult the
    ``REPRO_DISTRIB_SIM_GPU`` env var). ``device`` says this process owns
    an accelerator chip: only then is jax imported and the chip probed."""
    rng = np.random.default_rng(wid + 1)
    a = rng.normal(size=(n, n))
    b = rng.normal(size=(n, n))
    a @ b  # warm the BLAS path
    reps = 5
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        a @ b
        best = min(best, time.perf_counter() - t0)
    # best-of-N: scheduler noise only ever *slows* a rep, so the fastest
    # one is the honest capability number on a shared host
    gflops = 2.0 * n ** 3 / max(1e-9, best) / 1e9

    buf = rng.normal(size=1 << 20)          # 8 MB
    buf.copy()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        buf.copy()
        best = min(best, time.perf_counter() - t0)
    membw_gbs = 2.0 * buf.nbytes / max(1e-9, best) / 1e9  # read + write

    gpu_kind = device_kind = device_files = gpu_probe_error = ""
    visible_chips = ""
    gpu_gflops = h2d_gbs = d2h_gbs = 0.0
    if device:
        # echo the head's assignment (chip_env) as this process got it
        visible_chips = os.environ.get("TPU_VISIBLE_CHIPS", "")
        (gpu_kind, device_kind, device_files, gpu_gflops, h2d_gbs,
         d2h_gbs, gpu_probe_error) = _probe_device()
    has_gpu = bool(gpu_kind)
    if sim_gpu is None:
        sim_gpu = sim_gpu_for(wid)
    if sim_gpu and not has_gpu:
        # jax-CPU posing as a GPU (laptops/CI): capability tags and the
        # pricing table see a device ``factor``× faster than the host np
        # rate; execution stays on the jax CPU backend
        factor = float(os.environ.get("REPRO_DISTRIB_SIM_GPU_FACTOR",
                                      "4"))
        has_gpu, gpu_kind = True, "sim"
        gpu_gflops = round(gflops * max(0.1, factor), 3)
    return DeviceProfile(
        wid=wid,
        host=socket.gethostname(),
        pid=os.getpid(),
        cpus=os.cpu_count() or 1,
        mem_bytes=_probe_mem_bytes(),
        gflops=round(gflops, 3),
        membw_gbs=round(membw_gbs, 3),
        has_gpu=has_gpu,
        gpu_kind=gpu_kind,
        gpu_gflops=gpu_gflops,
        device_kind=device_kind,
        visible_chips=visible_chips,
        device_files=device_files,
        h2d_gbs=h2d_gbs,
        d2h_gbs=d2h_gbs,
        gpu_probe_error=gpu_probe_error,
    )
