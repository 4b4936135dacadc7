#!/usr/bin/env python3
"""Chip smoke: the compiled main path, end to end, on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the per-chip STAP fleet, four chips

Every phase runs in a child process of its own, and this parent never
imports JAX: a chip belongs to one process at a time, so each phase
gets it to itself. On one chip the phases are

  device   platform, device kind, device count and memory stats; fails
           when JAX finds no TPU;
  inproc   PolyBench/C 4.2.1 gemm and atax at LARGE through
           ``optimize()``: the dispatcher must pick the whole-kernel jnp
           variant, and its result must live on the TPU;
  cluster  adaptive STAP on a ``ClusterRuntime`` of two workers, one of
           which owns the chip, then matmul-, attention- and scan-shaped
           pfor kernels whose device chunks run the Pallas kernels
           compiled.

With ``--chips 4`` the phases are ``device`` and ``fleet4``: adaptive
STAP on four device workers, one chip each, against a one-worker run on
the same inputs.

Every result is checked against a NumPy float64 reference by normwise
relative error, ``|got - ref|_F / |ref|_F <= TOL``. Any failure exits
non-zero. The last line of the output is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# normwise relative error allowed against the float64 reference: the
# device computes in float32 (about 6e-8 per operation), and these
# sizes accumulate well under 1e-5 of it
TOL = 1e-5
SEED = 0

# PolyBench/C 4.2.1 LARGE: gemm (NI, NJ, NK) and atax (M, N)
GEMM = (1000, 1100, 1200)
ATAX = (1900, 2100)
# adaptive STAP (examples/stap.py) at the width of one chip's share:
# gates on one chip and on four, then K, DOF, ITERS, ALPHA, LOADING
STAP_GATES, STAP_GATES_4 = 2048, 8192
STAP_K, STAP_DOF, STAP_ITERS, STAP_ALPHA, STAP_LOADING = 256, 128, 800, \
    0.15, 2.0
# pallas-shaped pfor kernels: rows (all three), attention keys and head
# width, scan length; all aligned to the TPU's (8, 128) tiles
PALLAS_ROWS, ATTN_KEYS, ATTN_DIM, SCAN_LEN = 2048, 2048, 128, 1024

PHASE_TIMEOUT_S = 900
RESULT = "RESULT "


# ---------------------------------------------------------------------------
# helpers of the phase processes
# ---------------------------------------------------------------------------

class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def normwise(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def check_close(name: str, got, ref) -> float:
    err = normwise(got, ref)
    say(f"{name}: normwise error {err:.3e} (tolerance {TOL:g})")
    check(err <= TOL, f"{name}: normwise error {err:.3e} > {TOL:g}")
    return err


def chip_jax():
    """jax of a process that computes on the chip, compile cache on."""
    from repro.core.jaxcache import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    import jax

    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"no TPU found: JAX reports platform {dev.platform!r} "
          f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r})")
    return jax


def fleet(workers: int, device_workers: int):
    """A cluster whose first ``device_workers`` workers own a chip each
    (their hellos include the TPU runtime's start-up)."""
    from repro.distrib import ClusterRuntime

    return ClusterRuntime(workers=workers, device_workers=device_workers,
                          hello_timeout_s=600)


def stap_data(gates: int):
    import numpy as np

    rng = np.random.default_rng(SEED)
    train = rng.standard_normal((gates, STAP_K, STAP_DOF),
                                dtype=np.float32)
    snap = rng.standard_normal((gates, STAP_DOF), dtype=np.float32)
    steer = rng.standard_normal(STAP_DOF, dtype=np.float32)
    return snap, train, steer


def stap_reference(snap, train, steer):
    """MVDR beam outputs in float64. The kernel's Richardson iteration
    ``w <- w + alpha (s - R w - loading w)`` contracts with factor
    ``max |1 - alpha (lambda + loading)|`` over R's eigenvalues, checked
    below; after 800 steps it has converged to its fixed point, the
    solve ``(R + loading I) w = s``, which is the reference."""
    import numpy as np

    t = train.astype(np.float64)
    r = np.einsum("gki,gkj->gij", t, t) / STAP_K
    lam = np.linalg.eigvalsh(r)
    rho = float(np.max(np.abs(1.0 - STAP_ALPHA * (lam + STAP_LOADING))))
    say(f"stap reference: iteration contraction {rho:.3f}, "
        f"{STAP_ITERS} steps")
    check(rho < 0.9, f"Richardson iteration contracts too slowly ({rho})")
    a = r + STAP_LOADING * np.eye(STAP_DOF)
    s = np.broadcast_to(steer.astype(np.float64), (len(t), STAP_DOF))
    w = np.linalg.solve(a, s[..., None])[..., 0]
    return np.einsum("gi,gi->g", w, snap.astype(np.float64))


def run_stap(rt, gates: int, label: str):
    """Adaptive STAP through ``optimize()`` on the cluster ``rt``: the
    outputs of the second call, and the runtime's stats after each."""
    import numpy as np

    from benchmarks import chip_kernels as K
    from repro.core.compiler import optimize

    snap, train, steer = stap_data(gates)
    ck = optimize(runtime=rt, workers=rt.workers_alive())(K.stap_adaptive)
    ck.pfor_config.distribute_threshold = 0   # the cluster tier
    stats = []
    for call in ("first", "second"):
        out = np.zeros(gates, np.float32)
        t0 = time.perf_counter()
        ck(snap, train, steer, out, gates, STAP_K, STAP_DOF, STAP_ITERS,
           STAP_ALPHA, STAP_LOADING)
        st = rt.stats()
        stats.append(st)
        say(f"{label} {call} call: {time.perf_counter() - t0:.3f}s wall, "
            f"chunks_executed={st['chunks_executed']} "
            f"by_worker={st['chunks_executed_by_worker']} "
            f"jit_hits={st['jit_hits']} "
            f"jit_recompiles={st['jit_recompiles']} "
            f"jit_fallbacks={st['jit_fallbacks']} "
            f"jit_compile_s={st['jit_compile_s']:.3f}")
    return out, stats, (snap, train, steer)


def check_faults(rt) -> None:
    faults = rt.stats()["faults"]
    say(f"faults: {faults}")
    for kind in ("hello_failures", "degraded_local_runs", "worker_deaths",
                 "x64_enable_failed"):
        check(not faults.get(kind), f"fault {kind}: {faults.get(kind)}")


def print_fleet(rt) -> None:
    for p in rt.profiles():
        say(f"worker {p.wid}: platform={p.gpu_kind or 'cpu'} "
            f"kind={p.device_kind!r} visible_chips={p.visible_chips!r} "
            f"device_files={p.device_files!r} "
            f"device_gflops={p.gpu_gflops} h2d_gbs={p.h2d_gbs} "
            f"d2h_gbs={p.d2h_gbs} host_gflops={p.gflops} "
            f"probe_error={p.gpu_probe_error!r}")


# ---------------------------------------------------------------------------
# phases (each runs in its own process)
# ---------------------------------------------------------------------------

def phase_device() -> dict:
    jax = chip_jax()
    devs = jax.devices()
    dev = devs[0]
    say(f"platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devs)} jax={jax.__version__}")
    for d in devs:
        say(f"device {d.id}: coords={getattr(d, 'coords', None)} "
            f"memory_stats={d.memory_stats()}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def _spy_jnp_results(ck) -> list:
    """Record what the whole-kernel jnp variant returns, before the
    dispatcher copies it back into the caller's arrays."""
    variant = ck.variants["jnp"]
    inner = variant.fn
    seen: list = []

    def spy(*args):
        out = inner(*args)
        seen.append(out)
        return out

    variant.fn = spy
    return seen


def _check_inproc(name: str, ck, seen: list) -> None:
    rec = ck.history[-1]
    say(f"{name}: dispatch variant={rec.variant} flops={rec.flops:.3e}")
    check(rec.variant == "jnp", f"{name}: dispatched {rec.variant!r}")
    outs = seen[-1] if isinstance(seen[-1], tuple) else (seen[-1],)
    places = sorted({d.platform for o in outs
                     for d in getattr(o, "devices", set)()})
    say(f"{name}: variant result on {places}")
    check(places == ["tpu"], f"{name}: result on {places}, not the TPU")


def phase_inproc() -> dict:
    import numpy as np

    jax = chip_jax()
    import jax.numpy as jnp

    from benchmarks import chip_kernels as K
    from repro.core.compiler import optimize

    rng = np.random.default_rng(SEED)
    out = {}

    # gemm, PolyBench LARGE: C = alpha A B + beta C
    (ni, nj, nk), alpha, beta = GEMM, 1.5, 1.2
    a = rng.standard_normal((ni, nk), dtype=np.float32)
    b = rng.standard_normal((nk, nj), dtype=np.float32)
    c0 = rng.standard_normal((ni, nj), dtype=np.float32)
    ref = beta * c0.astype(np.float64) + alpha * (
        a.astype(np.float64) @ b.astype(np.float64))
    ck = optimize(K.gemm)
    seen = _spy_jnp_results(ck)
    for call in ("first", "second"):
        c = c0.copy()
        t0 = time.perf_counter()
        ck(alpha, beta, c, a, b, ni, nj, nk)
        say(f"gemm {call} call: {time.perf_counter() - t0:.3f}s wall")
    _check_inproc("gemm", ck, seen)
    out["gemm_err"] = check_close("gemm", c, ref)
    # the same product at the TPU's default matmul precision, for the
    # record: why the device bodies pin theirs
    dflt = beta * c0 + alpha * jnp.dot(jnp.asarray(a), jnp.asarray(b))
    say(f"gemm at the TPU's default matmul precision: normwise error "
        f"{normwise(dflt, ref):.3e}")

    # atax, PolyBench LARGE: y = A^T (A x)
    m, n = ATAX
    a = rng.standard_normal((m, n), dtype=np.float32)
    x = rng.standard_normal(n, dtype=np.float32)
    a64 = a.astype(np.float64)
    ref = a64.T @ (a64 @ x.astype(np.float64))
    ck = optimize(K.atax)
    seen = _spy_jnp_results(ck)
    for call in ("first", "second"):
        y = np.zeros(n, np.float32)
        tmp = np.zeros(m, np.float32)
        t0 = time.perf_counter()
        ck(a, x, y, tmp, m, n)
        say(f"atax {call} call: {time.perf_counter() - t0:.3f}s wall")
    _check_inproc("atax", ck, seen)
    out["atax_err"] = check_close("atax", y, ref)
    return out


def _pallas_case(rt, name: str, kernel, args, result, ref,
                 steer_flops: bool = False) -> float:
    """Run one pallas-shaped pfor kernel on the cluster and require that
    its device chunks ran the compiled Pallas kernel."""
    from repro.core.compiler import optimize

    ck = optimize(runtime=rt, workers=rt.workers_alive())(kernel)
    ck.pfor_config.distribute_threshold = 0
    check("pallas" in ck.pfor_twin_units(), f"{name}: no pallas twin")
    if steer_flops:
        # a memory-bound unit: the roofline prices its chunks to np on
        # the host, so the estimate is zeroed and routing falls to the
        # highest-priority backend each worker can run
        ck.estimate_flops = lambda bound: 0.0
    before = rt.stats()
    t0 = time.perf_counter()
    ck(*args)
    wall = time.perf_counter() - t0
    st = rt.stats()

    def delta(key):
        return st[key] - before[key]

    ran = {k: v - before["chunks_executed"].get(k, 0)
           for k, v in st["chunks_executed"].items()}
    say(f"{name}: {wall:.3f}s wall (compiles included), "
        f"chunks_executed={ran} pallas_calls={delta('pallas_calls')} "
        f"pallas_interpret_calls={delta('pallas_interpret_calls')} "
        f"pallas_fallbacks={delta('pallas_fallbacks')}")
    check(ran.get("pallas", 0) > 0, f"{name}: no chunk ran on pallas")
    check(delta("pallas_interpret_calls") == 0,
          f"{name}: pallas kernels ran interpreted")
    check(delta("pallas_fallbacks") == 0, f"{name}: pallas fell back")
    return check_close(name, result, ref)


def phase_cluster() -> dict:
    import numpy as np

    from benchmarks import chip_kernels as K
    from repro.core.jaxcache import enable_compile_cache

    enable_compile_cache()
    out = {}
    rt = fleet(2, 1)
    try:
        print_fleet(rt)
        check(sum(p.gpu_kind == "tpu" for p in rt.profiles()) == 1,
              "the fleet has no TPU worker")

        got, stats, (snap, train, steer) = run_stap(rt, STAP_GATES,
                                                     "stap")
        say(f"stap: training tensor {train.nbytes / 2**20:.0f} MiB")
        first, second = stats
        check(second["chunks_executed"].get("jnp", 0) > 0,
              "stap: no chunk ran the jnp twin")
        check(second["jit_hits"] > first["jit_hits"],
              "stap: the second call hit no compiled executable")
        check(second["jit_fallbacks"] == 0, "stap: jit fell back")
        out["stap_err"] = check_close(
            "stap", got, stap_reference(snap, train, steer))
        out["stap_jit_compile_s"] = second["jit_compile_s"]

        rng = np.random.default_rng(SEED)
        n = PALLAS_ROWS
        a = rng.standard_normal((n, n), dtype=np.float32)
        b = rng.standard_normal((n, n), dtype=np.float32)
        c = np.zeros((n, n), np.float32)
        out["gemm_rowscale_err"] = _pallas_case(
            rt, "gemm_rowscale", K.gemm_rowscale, (a, b, c, n, n, n), c,
            2.0 * a.astype(np.float64) @ b.astype(np.float64))

        t, d = ATTN_KEYS, ATTN_DIM
        q, k, v = (rng.standard_normal((n, d), dtype=np.float32)
                   / np.float32(d ** 0.25) for _ in range(3))
        o = np.zeros((n, d), np.float32)
        s = np.exp(q.astype(np.float64) @ k.astype(np.float64).T)
        ref = (s @ v.astype(np.float64)) / s.sum(axis=1, keepdims=True)
        out["attn_err"] = _pallas_case(rt, "attn", K.attn,
                                       (q, k, v, o, n, t, d), o, ref)

        length = SCAN_LEN
        x = rng.standard_normal((n, length), dtype=np.float32)
        y = np.zeros((n, length), np.float32)
        ref = np.zeros((n, length))
        h = np.zeros(n)
        for step in range(length):
            h = 0.9 * h + x[:, step]
            ref[:, step] = h
        out["scan_err"] = _pallas_case(rt, "scan", K.scan,
                                       (x, y, n, length), y, ref,
                                       steer_flops=True)
        check_faults(rt)
    finally:
        rt.shutdown()
    return out


def phase_fleet4() -> dict:
    from repro.core.jaxcache import enable_compile_cache

    enable_compile_cache()
    gates = STAP_GATES_4
    out = {}
    rt = fleet(4, 4)
    try:
        print_fleet(rt)
        got4, stats, _ = run_stap(rt, gates, "stap 4 chips")
        # each owner is shown one chip (it sees it as chip 0), so the
        # chips are told apart by the head's assignment, as the worker
        # echoes it, and by the device files the worker holds open
        owners = {p.wid: p for p in rt.profiles() if p.gpu_kind == "tpu"}
        ran = sorted({int(w) for w, c in
                      stats[-1]["chunks_executed_by_worker"].items()
                      if c > 0} & owners.keys())
        chips = [owners[w].visible_chips for w in ran]
        files = [owners[w].device_files for w in ran]
        say(f"stap 4 chips: TPU-owning workers that ran chunks: {ran}, "
            f"visible chips {chips}, device files {files}")
        check(len(set(chips)) == 4 and "" not in chips,
              f"stap ran on chips {chips}, not 4 distinct chips")
        check(len(set(files)) == 4 and "" not in files,
              f"device files {files} do not show 4 distinct chips")
        check(stats[-1]["jit_fallbacks"] == 0, "stap: jit fell back")
        check_faults(rt)
    finally:
        rt.shutdown()
    rt = fleet(1, 1)
    try:
        print_fleet(rt)
        got1, _, _ = run_stap(rt, gates, "stap 1 chip")
        check_faults(rt)
    finally:
        rt.shutdown()
    out["stap4_vs_1_err"] = check_close("stap 4 chips vs 1 chip", got4,
                                        got1)
    return out


PHASES = {"device": phase_device, "inproc": phase_inproc,
          "cluster": phase_cluster, "fleet4": phase_fleet4}


def run_child(phase: str) -> int:
    try:
        result = PHASES[phase]()
    except SmokeFailure as exc:
        say(f"FAILED: {exc}")
        return 1
    say(RESULT + json.dumps(result))
    return 0


# ---------------------------------------------------------------------------
# parent: no JAX here
# ---------------------------------------------------------------------------

def run_phase(phase: str):
    """Run one phase in a child process; its result dict, or None."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT)]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    say(f"== phase {phase}")
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        [sys.executable, str(Path(__file__).resolve()), "--phase", phase],
        cwd=str(ROOT), env=env, stdout=subprocess.PIPE, text=True,
        start_new_session=True)

    def kill_group():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    timer = threading.Timer(PHASE_TIMEOUT_S, kill_group)
    timer.start()
    result = None
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line.startswith(RESULT):
                result = json.loads(line[len(RESULT):])
            else:
                say(f"[{phase}] {line}")
        rc = proc.wait()
    finally:
        timer.cancel()
        kill_group()   # workers the phase may have left behind
    say(f"== phase {phase}: rc={rc} in {time.perf_counter() - t0:.1f}s")
    return result if rc == 0 else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return run_child(args.phase)
    if not (ROOT / "src" / "repro").is_dir():
        say(f"chip_smoke.py needs the repository around it: no "
            f"{ROOT / 'src' / 'repro'}")
        return 2
    phases = (["device", "inproc", "cluster"] if args.chips == 1
              else ["device", "fleet4"])
    results = {}
    for phase in phases:
        res = run_phase(phase)
        if res is None:
            say(f"chip smoke FAILED in phase {phase}")
            return 1
        results[phase] = res
    device = results["device"]
    if device["count"] < args.chips:
        say(f"chip smoke FAILED: {args.chips} chips asked for, "
            f"{device['count']} found")
        return 1
    say(f"results: {json.dumps(results)}")
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
