"""STAP radar benchmark — the paper's §5.3 pipeline at container scale.

Pipeline per data cube (paper Fig. 7): beamforming (steer-vector ×
channels matmul) → Doppler FFT → match-filter multiply. ``run()`` times
the original sequential NumPy implementation; ``--distrib``,
``--hetero``, ``--pallas`` and ``--chaos`` run the compiler's
auto-parallelized version (the cube loop detected as pfor and sharded
over :class:`repro.distrib.ClusterRuntime` worker processes).

Reported metric: cubes/sec (the paper's real-time requirement is 33.3
cubes/sec at full problem size; we also report our scaled-size numbers
against a proportionally scaled requirement).
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

# scaled-down cube (paper: pulses=100, channels=1000, samples=30000 —
# 24 GB/cube complex128; here ~4 MB/cube so the suite runs on one core)
CHANNELS = 64
SAMPLES = 4096
FFT_SIZE = 8192
N_CUBES = 24

# full-size scaling factor for the real-time-requirement comparison
PAPER_CUBE_FLOPS = (100 * 1000 * 30000 * 8          # beamform
                    + 100 * 5 * 30000 * 15          # fft (nlogn-ish)
                    + 100 * 30000 * 6)
OUR_CUBE_FLOPS = (CHANNELS * SAMPLES * 8
                  + 5 * FFT_SIZE * 13 + FFT_SIZE * 6)


def stap_kernel(dataCubes: "ndarray[c128,3]", steerVector: "ndarray[c128,1]",
                matchFilter: "ndarray[c128,2]", outY: "ndarray[c128,2]",
                numCubes: int, fftSize: int):
    for c in range(0, numCubes):
        bf = np.dot(steerVector, dataCubes[c, 0:steerVector.shape[0], :])
        X = np.fft.fft(bf, fftSize)
        outY[c, 0:fftSize] = X * matchFilter[c, 0:fftSize]


def stap_ref(dataCubes, steerVector, matchFilter, outY, numCubes,
             fftSize):
    for c in range(numCubes):
        bf = steerVector @ dataCubes[c]
        X = np.fft.fft(bf, fftSize)
        outY[c] = X * matchFilter[c]


def make_data(n_cubes=N_CUBES, seed=5):
    rng = np.random.default_rng(seed)
    cubes = (rng.normal(size=(n_cubes, CHANNELS, SAMPLES))
             + 1j * rng.normal(size=(n_cubes, CHANNELS, SAMPLES)))
    sv = rng.normal(size=CHANNELS) + 1j * rng.normal(size=CHANNELS)
    mf = (rng.normal(size=(n_cubes, FFT_SIZE))
          + 1j * rng.normal(size=(n_cubes, FFT_SIZE)))
    out = np.zeros((n_cubes, FFT_SIZE), complex)
    return cubes, sv, mf, out


def run(csv: bool = True) -> List[Dict]:
    cubes, sv, mf, out = make_data()
    rows = []

    # -- sequential numpy baseline ---------------------------------------
    out_ref = out.copy()
    t0 = time.perf_counter()
    stap_ref(cubes, sv, mf, out_ref, N_CUBES, FFT_SIZE)
    t_seq = time.perf_counter() - t0
    rows.append({"variant": "python_numpy", "workers": 1,
                 "cubes_per_s": N_CUBES / t_seq})

    if csv:
        for r in rows:
            print(f"stap.{r['variant']},workers={r['workers']},"
                  f"{r['cubes_per_s']:.2f}_cubes_per_s", flush=True)
        scale = PAPER_CUBE_FLOPS / OUR_CUBE_FLOPS
        print(f"stap.scale_note,paper_cube/our_cube_flops={scale:.0f}x,"
              f"realtime_req_scaled={33.3 / 1:.1f}_cubes_per_s_at_full_size")
    return rows


def _phase_delta(before: Dict[str, float],
                 after: Dict[str, float]) -> Dict[str, float]:
    """Per-phase seconds attributable to one timed call (the cluster's
    phase counters are cumulative)."""
    return {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}


def _trace_diagnosis(delta: Dict[str, float], wall_s: float,
                     workers: int) -> str:
    """One-line, trace-derived explanation of where a cluster round's
    wall time went — the 'why is this row slow' statement."""
    round_s = delta.get("round_s", 0.0) or wall_s
    head = {k[:-2]: v for k, v in delta.items()
            if k in ("plan_s", "split_s", "dispatch_s", "gather_s",
                     "merge_s")}
    parts = dict(head)
    if "compute_s" in delta:
        # worker compute is summed across workers: normalize to the
        # head's wall by dividing by the worker count
        parts["compute"] = delta["compute_s"] / max(1, workers)
    name, secs = max(parts.items(), key=lambda kv: kv[1])
    pct = 100.0 * secs / round_s if round_s > 0 else 0.0
    where = "on head" if name in head else f"across {workers} workers"
    return (f"{name} {where} = {pct:.0f}% of round wall "
            f"({secs * 1e3:.1f}ms of {round_s * 1e3:.1f}ms)")


def run_distrib(smoke: bool = False, out_path: str = "BENCH_distrib.json",
                trace_path: str = "TRACE_distrib.json") -> List[Dict]:
    """Adaptive STAP (examples/stap.py) on the multi-process cluster
    runtime: sequential vs 1-process vs N-process, measured — no
    simulated dimension. Writes ``BENCH_distrib.json`` and (for the
    widest cluster run, which is traced) the Perfetto timeline
    ``TRACE_distrib.json`` — feed it to ``python -m
    repro.obs.summarize`` for the per-phase breakdown."""
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from examples.stap import (ALPHA, ITERS, LOADING, make_stap_data,
                               stap_adaptive, stap_seq)
    from repro import obs
    from repro.core.compiler import compile_kernel
    from repro.distrib import ClusterRuntime

    if smoke:
        gates, k, dof, iters = 16, 16, 16, 30
    else:
        gates, k, dof, iters = 96, 64, 64, ITERS
    snap, train, steer, out = make_stap_data(gates, k, dof)

    reps = 1 if smoke else 3   # best-of-N: the container is noisy

    rows: List[Dict] = []
    out_ref = out.copy()
    t_seq = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        stap_seq(snap, train, steer, out_ref, gates, k, dof, iters,
                 ALPHA, LOADING)
        t_seq = min(t_seq, time.perf_counter() - t0)
    rows.append({"variant": "sequential_numpy", "workers": 0,
                 "wall_s": round(t_seq, 5),
                 "gates_per_s": round(gates / t_seq, 2),
                 "speedup_vs_seq": 1.0, "measured": True})

    fleet = (1, 2) if smoke else (1, 2, 4)
    for workers in fleet:
        # every cluster run is traced (compute/idle need worker spans);
        # the widest run gets a fresh recorder and exports the Perfetto
        # timeline at shutdown, so the artifact is one clean fleet run
        last = workers == fleet[-1]
        if last:
            obs.enable()
            obs.recorder().clear()
        rt = ClusterRuntime(workers=workers,
                            trace=trace_path if last else True)
        try:
            ck = compile_kernel(stap_adaptive, runtime=rt,
                                workers=workers)
            ck.pfor_config.distribute_threshold = 0
            out_a = out.copy()
            ck.call_variant("np", snap, train, steer, out_a, gates, k,
                            dof, iters, ALPHA, LOADING)  # warm workers
            t_n = float("inf")
            phases: Dict[str, float] = {}
            for _ in range(reps):
                out_a = out.copy()
                ph0 = rt.phase_breakdown()
                t0 = time.perf_counter()
                ck.call_variant("np", snap, train, steer, out_a, gates,
                                k, dof, iters, ALPHA, LOADING)
                t_rep = time.perf_counter() - t0
                if t_rep < t_n:
                    t_n = t_rep
                    phases = _phase_delta(ph0, rt.phase_breakdown())
            err = float(abs(out_a - out_ref).max())
            assert err < 1e-8, f"distributed STAP mismatch: {err:.2e}"
            st = rt.stats()
            # data-movement contract: sliceable args actually sliced,
            # and the repeated calls above hit the persistent blob cache
            # (the warm call is the one miss) without re-shipping
            # unchanged cells
            assert st["sliced_args"] > 0, st
            assert st["blob_hits"] > 0, st
            assert st["cells_skipped"] > 0, st
            rows.append({
                "variant": "cluster", "workers": workers,
                "wall_s": round(t_n, 5),
                "gates_per_s": round(gates / t_n, 2),
                "speedup_vs_seq": round(t_seq / t_n, 3),
                "max_abs_err": err, "measured": True,
                "chunks": st["chunks_dispatched"],
                "bytes_shipped": st["bytes_shipped"],
                "bytes_saved_sliced": st["bytes_saved_sliced"],
                "sliced_args": st["sliced_args"],
                "blob_hits": st["blob_hits"],
                "blob_misses": st["blob_misses"],
                "cells_shipped": st["cells_shipped"],
                "cells_skipped": st["cells_skipped"],
                "profiles_gflops": [p.gflops for p in rt.profiles()],
                # trace-plane phase breakdown for the best rep
                "ship_s": round(phases.get("ship_s", 0.0), 5),
                "gather_s": round(phases.get("gather_s", 0.0), 5),
                "compute_s": round(phases.get("compute_s", 0.0), 5),
                "idle_s": round(phases.get("idle_s", 0.0), 5),
                "phases": {k: round(v, 5) for k, v in phases.items()},
                "diagnosis": _trace_diagnosis(phases, t_n, workers),
            })
        finally:
            rt.shutdown()

    doc = {"workload": "stap_adaptive",
           "shape": {"gates": gates, "k_train": k, "dof": dof,
                     "iters": iters},
           "smoke": smoke, "rows": rows}
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    for r in rows:
        extra = ""
        if r["variant"] == "cluster":
            extra = (f",shipped={r['bytes_shipped']}B"
                     f",saved_sliced={r['bytes_saved_sliced']}B"
                     f",blob_hits={r['blob_hits']}")
        print(f"stap_distrib.{r['variant']},workers={r['workers']},"
              f"{r['gates_per_s']}_gates_per_s,"
              f"x{r['speedup_vs_seq']}{extra}", flush=True)
        if r.get("diagnosis"):
            print(f"stap_distrib.diagnosis,workers={r['workers']},"
                  f"{r['diagnosis']}", flush=True)
    print(f"stap_distrib.written,{out_path}")
    print(f"stap_distrib.trace_written,{trace_path}")
    return rows


def run_hetero(smoke: bool = False, out_path: str = "BENCH_distrib.json"
               ) -> List[Dict]:
    """Heterogeneous fleet: 1 CPU worker + 1 simulated-GPU worker (jax
    CPU posing via the ``has_gpu`` profile override) running the *same*
    compiled pfor — per-worker backend selection (np vs jnp twin
    bodies), chunks sized by chosen-backend throughput, one gathered
    result. Appends measured ``cluster_hetero`` rows to
    ``BENCH_distrib.json`` (regular ``--distrib`` rows are preserved).

    The simulated GPU runs jnp *eagerly on the CPU*, so the hetero rows
    measure routing + gather overhead, not accelerator speedup — they
    are labeled ``simulated_gpu: true``."""
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from examples.stap import (ALPHA, LOADING, make_stap_data,
                               stap_adaptive, stap_seq)
    from repro import obs
    from repro.core.compiler import compile_kernel
    from repro.distrib import ClusterRuntime

    if smoke:
        # large enough that per-round compute dominates dispatch/IPC
        # overhead — the smoke CI asserts compare fleet variants'
        # throughput, which is pure noise at tiny shapes
        gates, k, dof, iters = 32, 32, 32, 80
    else:
        gates, k, dof, iters = 48, 32, 32, 120
    snap, train, steer, out = make_stap_data(gates, k, dof)
    reps = 2 if smoke else 3

    out_ref = out.copy()
    t_seq = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        stap_seq(snap, train, steer, out_ref, gates, k, dof, iters,
                 ALPHA, LOADING)
        t_seq = min(t_seq, time.perf_counter() - t0)

    rows: List[Dict] = []

    def fleet_row(variant: str, workers: int, sim_gpus,
                  np_only: bool = False, trace: bool = False) -> Dict:
        """One serving-loop measurement on a fresh fleet: warm call to
        ship blobs + compile the jitted twins, then best-of-reps."""
        rt = ClusterRuntime(workers=workers, sim_gpu_workers=sim_gpus,
                            np_only=np_only, trace=trace)
        try:
            comp = obs.metrics.scope("compile.stap_adaptive")
            c0 = sum(comp.snapshot().values())
            ck = compile_kernel(stap_adaptive, runtime=rt,
                                workers=workers)
            compile_s = sum(comp.snapshot().values()) - c0
            ck.pfor_config.distribute_threshold = 0
            out_a = out.copy()
            ck.call_variant("np", snap, train, steer, out_a, gates, k,
                            dof, iters, ALPHA, LOADING)   # warm
            t_h = float("inf")
            phases: Dict[str, float] = {}
            for _ in range(reps):
                out_a = out.copy()
                ph0 = rt.phase_breakdown()
                t0 = time.perf_counter()
                ck.call_variant("np", snap, train, steer, out_a, gates,
                                k, dof, iters, ALPHA, LOADING)
                t_rep = time.perf_counter() - t0
                if t_rep < t_h:
                    t_h = t_rep
                    phases = _phase_delta(ph0, rt.phase_breakdown())
            err = float(abs(out_a - out_ref).max())
            assert err < 1e-8, f"{variant} STAP mismatch: {err:.2e}"
            st = rt.stats()
            profs = rt.profiles()
            row = {
                "variant": variant, "workers": workers,
                "simulated_gpu": bool(sim_gpus),
                "np_only": np_only,
                "wall_s": round(t_h, 5),
                "gates_per_s": round(gates / t_h, 2),
                "speedup_vs_seq": round(t_seq / t_h, 3),
                "max_abs_err": err, "measured": True,
                "gpu_chunks": st["gpu_chunks"],
                "cpu_chunks": st["cpu_chunks"],
                "chunks_executed": st["chunks_executed"],
                "unit_backend": st["unit_backend"],
                "blob_hits": st["blob_hits"],
                "blob_misses": st["blob_misses"],
                "bytes_shipped": st["bytes_shipped"],
                # accelerated-path telemetry (ISSUE 9): compiled-twin
                # cache behavior, device residency, row re-ship skips,
                # and gather/compute overlap from pipelined rounds
                "jit_hits": st["jit_hits"],
                "jit_recompiles": st["jit_recompiles"],
                "jit_fallbacks": st["jit_fallbacks"],
                "resident_hits": st["resident_hits"],
                "resident_cells": st["resident_cells"],
                "rows_skipped": st["rows_skipped"],
                "bytes_saved_rows": st["bytes_saved_rows"],
                "pipeline_depth": st["pipeline_depth"],
                "overlap_s": round(phases.get("overlap_s", 0.0), 5),
                "profiles": [{"gflops": p.gflops, "has_gpu": p.has_gpu,
                              "gpu_gflops": p.gpu_gflops,
                              "gpu_kind": p.gpu_kind} for p in profs],
                "compile_s": round(compile_s, 5),
                "ship_s": round(phases.get("ship_s", 0.0), 5),
                "gather_s": round(phases.get("gather_s", 0.0), 5),
                "compute_s": round(phases.get("compute_s", 0.0), 5),
                "idle_s": round(phases.get("idle_s", 0.0), 5),
                "phases": {k_: round(v, 5) for k_, v in phases.items()},
            }
            if trace:
                row["diagnosis"] = _trace_diagnosis(phases, t_h,
                                                    workers)
            return row
        finally:
            rt.shutdown()

    # control arm: the same posed fleet with twin routing suppressed —
    # the bar cluster_hetero must clear to claim the accelerator helps
    rows.append(fleet_row("cluster_np_only", 2, (1,), np_only=True))
    # traced: the hetero row's historically terrible speedup (0.006x
    # pre-fix) needs the span timeline to say *why*, not just how fast
    hetero = fleet_row("cluster_hetero", 2, (1,), trace=True)
    rows.append(hetero)
    # scaling arm: twice the fleet (2 CPU + 2 posed GPU)
    rows.append(fleet_row("cluster_hetero_4w", 4, (1, 3)))

    # the heterogeneity contract: the same pfor *executed* np chunks on
    # the CPU worker and jnp chunks on the GPU-posing worker (confirmed
    # by worker done-messages, not dispatch intent), the persistent
    # blobs survived the serving loop, and the serving loop ran on the
    # compiled twin path (jit cache hits, no eager fallbacks)
    assert hetero["chunks_executed"].get("np", 0) > 0, hetero
    assert hetero["chunks_executed"].get("jnp", 0) > 0, hetero
    assert hetero["gpu_chunks"] > 0 and hetero["cpu_chunks"] > 0, hetero
    assert hetero["blob_hits"] > 0, hetero
    assert hetero["jit_hits"] > 0, hetero

    rows.insert(0, {"variant": "sequential_numpy_hetero_ref",
                    "workers": 0, "wall_s": round(t_seq, 5),
                    "gates_per_s": round(gates / t_seq, 2),
                    "speedup_vs_seq": 1.0, "measured": True})
    try:
        with open(out_path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {"workload": "stap_adaptive", "rows": []}
    doc["rows"] = [r for r in doc.get("rows", [])
                   if r.get("variant") not in
                   ("cluster_hetero", "cluster_np_only",
                    "cluster_hetero_4w", "sequential_numpy_hetero_ref")]
    doc["rows"].extend(rows)
    doc["hetero_shape"] = {"gates": gates, "k_train": k, "dof": dof,
                           "iters": iters, "smoke": smoke}
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    for r in rows:
        extra = ""
        if r["variant"].startswith("cluster_"):
            extra = (f",gpu_chunks={r['gpu_chunks']}"
                     f",cpu_chunks={r['cpu_chunks']}"
                     f",blob_hits={r['blob_hits']}"
                     f",jit_hits={r['jit_hits']}"
                     f",rows_skipped={r['rows_skipped']}")
        print(f"stap_hetero.{r['variant']},workers={r['workers']},"
              f"{r['gates_per_s']}_gates_per_s,"
              f"x{r['speedup_vs_seq']}{extra}", flush=True)
        if r.get("diagnosis"):
            print(f"stap_hetero.diagnosis,x{r['speedup_vs_seq']},"
                  f"{r['diagnosis']}", flush=True)
    print(f"stap_hetero.written,{out_path}")
    return rows


def gemm_rowscale(A: "ndarray[f64,2]", B: "ndarray[f64,2]",
                  C: "ndarray[f64,2]", n: int, k: int, m: int):
    """Matmul-shaped pfor for the pallas routing benchmark: the scaled
    row keeps the dot statement inside a pfor body (a bare single-dot
    loop is absorbed into a top-level raised unit), and the pattern
    matcher fuses the scale into the ``__plk.matmul`` operand."""
    for i in range(0, n):
        r = 2.0 * A[i, 0:k]
        C[i, 0:m] = np.dot(r, B[0:k, 0:m])


def run_pallas(smoke: bool = False,
               out_path: str = "BENCH_distrib.json") -> List[Dict]:
    """Pallas-backend routing benchmark: a matmul-shaped pfor on a
    simulated-GPU fleet must route its chunks to the pallas backend
    (roofline-priced above np/jnp via the fused-kernel speedup) and
    produce results identical to the np-only control arm. Appends a
    measured ``cluster_pallas`` row (plus its control) to
    ``BENCH_distrib.json``.

    On CPU-only hosts the kernels run in interpret mode, so the row
    measures routing + gather overhead, not kernel speedup — labeled
    ``simulated_gpu: true`` like the hetero rows."""
    import json

    from repro.core.compiler import compile_kernel
    from repro.distrib import ClusterRuntime

    if smoke:
        n, k, m, reps = 192, 48, 40, 2
    else:
        n, k, m, reps = 384, 64, 56, 3
    rng = np.random.default_rng(42)
    A = rng.normal(size=(n, k))
    B = rng.normal(size=(k, m))

    ref = np.zeros((n, m))
    t_seq = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        gemm_rowscale(A, B, ref, n, k, m)
        t_seq = min(t_seq, time.perf_counter() - t0)

    rows: List[Dict] = []

    def fleet_row(variant: str, sim_gpus, np_only: bool = False) -> Dict:
        rt = ClusterRuntime(workers=2, sim_gpu_workers=sim_gpus,
                            np_only=np_only)
        try:
            ck = compile_kernel(gemm_rowscale, runtime=rt, workers=2)
            ck.pfor_config.distribute_threshold = 0
            C = np.zeros((n, m))
            ck.call_variant("np", A, B, C, n, k, m)      # warm
            t_w = float("inf")
            for _ in range(reps):
                C = np.zeros((n, m))
                t0 = time.perf_counter()
                ck.call_variant("np", A, B, C, n, k, m)
                t_w = min(t_w, time.perf_counter() - t0)
            err = float(abs(C - ref).max())
            assert err < 1e-8, f"{variant} matmul mismatch: {err:.2e}"
            st = rt.stats()
            return {
                "variant": variant, "workers": 2,
                "simulated_gpu": bool(sim_gpus),
                "np_only": np_only,
                "wall_s": round(t_w, 5),
                "rows_per_s": round(n / t_w, 2),
                "speedup_vs_seq": round(t_seq / t_w, 3),
                "max_abs_err": err, "measured": True,
                "chunks_executed": st["chunks_executed"],
                "unit_backend": st["unit_backend"],
                "pallas_chunks": st["pallas_chunks"],
                "pallas_fallbacks": st["pallas_fallbacks"],
                "pallas_calls": st["pallas_calls"],
                "pallas_interpret_calls": st["pallas_interpret_calls"],
                "gpu_chunks": st["gpu_chunks"],
                "cpu_chunks": st["cpu_chunks"],
                "blob_hits": st["blob_hits"],
            }
        finally:
            rt.shutdown()

    rows.append(fleet_row("cluster_pallas_np_only", (0, 1),
                          np_only=True))
    pal = fleet_row("cluster_pallas", (0, 1))
    rows.append(pal)

    # the routing contract: chunks *executed* on the pallas backend
    # (confirmed by worker done-messages), no fallbacks burned, and the
    # np-only control produced the same answer (asserted above vs ref)
    assert pal["chunks_executed"].get("pallas", 0) > 0, pal
    assert pal["pallas_chunks"] > 0, pal
    assert pal["pallas_fallbacks"] == 0, pal
    assert pal["pallas_calls"] > 0, pal

    try:
        with open(out_path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {"workload": "stap_adaptive", "rows": []}
    doc["rows"] = [r for r in doc.get("rows", [])
                   if r.get("variant") not in
                   ("cluster_pallas", "cluster_pallas_np_only")]
    doc["rows"].extend(rows)
    doc["pallas_shape"] = {"n": n, "k": k, "m": m, "smoke": smoke}
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    for r in rows:
        print(f"stap_pallas.{r['variant']},workers={r['workers']},"
              f"{r['rows_per_s']}_rows_per_s,"
              f"x{r['speedup_vs_seq']},"
              f"pallas_chunks={r['pallas_chunks']},"
              f"fallbacks={r['pallas_fallbacks']}", flush=True)
    print(f"stap_pallas.written,{out_path}")
    return rows


def run_chaos(smoke: bool = False,
              out_path: str = "FAULTS_distrib.json") -> Dict:
    """Fault-injection drill: the STAP serving loop over the TCP
    transport with seeded chaos — a worker SIGKILLed mid-loop, a worker
    joining mid-loop, and every head→worker message delayed — must keep
    producing atol-1e-8-correct answers with zero head-side exceptions,
    and the joined worker must visibly take a share of the chunks.

    A second drill collapses the whole fleet with respawn disabled and
    checks the runtime degrades to correct local execution. The fault
    journal + recovery counters are written to ``FAULTS_distrib.json``
    (uploaded beside ``BENCH_distrib.json`` in CI)."""
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from examples.stap import (ALPHA, LOADING, make_stap_data,
                               stap_adaptive, stap_seq)
    from repro.core.compiler import compile_kernel
    from repro.distrib import ChaosPlan, ClusterRuntime

    if smoke:
        gates, k, dof, iters = 16, 16, 16, 30
        calls = 8
    else:
        gates, k, dof, iters = 48, 32, 32, 120
        calls = 12
    snap, train, steer, out = make_stap_data(gates, k, dof)
    out_ref = out.copy()
    stap_seq(snap, train, steer, out_ref, gates, k, dof, iters,
             ALPHA, LOADING)

    plan = ChaosPlan(seed=7, delay_s=0.002)   # every message delayed
    kill_at, join_at = 3, calls // 2
    joined_wid = None
    rt = ClusterRuntime(workers=2, transport="tcp", respawn=True,
                        hb_interval_s=0.2, reconnect_grace_s=1.0,
                        chaos=plan)
    try:
        ck = compile_kernel(stap_adaptive, runtime=rt, workers=2)
        ck.pfor_config.distribute_threshold = 0
        for call in range(calls):
            if call == kill_at:
                print(f"stap_chaos.kill,call={call},"
                      f"wid={rt.kill_worker()}", flush=True)
            if call == join_at:
                joined_wid = rt.add_worker()
                print(f"stap_chaos.join,call={call},wid={joined_wid}",
                      flush=True)
            out_a = out.copy()
            ck.call_variant("np", snap, train, steer, out_a, gates, k,
                            dof, iters, ALPHA, LOADING)
            err = float(abs(out_a - out_ref).max())
            assert err < 1e-8, \
                f"chaos STAP mismatch at call {call}: {err:.2e}"
        st = rt.stats()
        by_worker = dict(st["chunks_executed_by_worker"])
        assert st["worker_deaths"] >= 1, st["faults"]
        assert st["faults"].get("respawns", 0) >= 1, st["faults"]
        assert st["faults"].get("joins", 0) >= 1, st["faults"]
        assert plan.delayed > 0, plan.stats()
        assert joined_wid in by_worker and by_worker[joined_wid] > 0, \
            f"joined worker {joined_wid} got no chunks: {by_worker}"
        serving = {"calls": calls, "kill_at_call": kill_at,
                   "join_at_call": join_at, "joined_wid": joined_wid,
                   "max_abs_err": err,
                   "chunks_executed_by_worker": by_worker,
                   "worker_deaths": st["worker_deaths"],
                   "faults": st["faults"], "chaos": plan.stats()}
        events = list(rt.fault_events)
    finally:
        rt.shutdown()

    # fleet collapse with respawn off: correctness must survive via
    # in-process degradation, not hang or raise
    rt = ClusterRuntime(workers=2, respawn=False)
    try:
        ck = compile_kernel(stap_adaptive, runtime=rt, workers=2)
        ck.pfor_config.distribute_threshold = 0
        while rt.kill_worker() is not None:
            pass
        deadline = time.perf_counter() + 10.0
        while rt.workers_alive() > 0 and time.perf_counter() < deadline:
            time.sleep(0.05)
        out_a = out.copy()
        ck.call_variant("np", snap, train, steer, out_a, gates, k, dof,
                        iters, ALPHA, LOADING)
        err = float(abs(out_a - out_ref).max())
        assert err < 1e-8, f"degraded STAP mismatch: {err:.2e}"
        st = rt.stats()
        degraded = (st["faults"].get("degraded_local_runs", 0)
                    + st["faults"].get("degraded_chunks", 0))
        assert degraded >= 1, st["faults"]
        degrade = {"max_abs_err": err, "faults": st["faults"]}
        events += list(rt.fault_events)
    finally:
        rt.shutdown()

    doc = {"workload": "stap_adaptive_chaos",
           "shape": {"gates": gates, "k_train": k, "dof": dof,
                     "iters": iters}, "smoke": smoke,
           "serving_loop": serving, "degrade_drill": degrade,
           "events": events}
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"stap_chaos.serving,calls={calls},max_abs_err={err:.2e},"
          f"deaths={serving['worker_deaths']},"
          f"respawns={serving['faults'].get('respawns', 0)},"
          f"delayed_msgs={serving['chaos']['delayed']}", flush=True)
    print(f"stap_chaos.rebalance,{serving['chunks_executed_by_worker']}",
          flush=True)
    print(f"stap_chaos.degrade,"
          f"local_runs={degrade['faults'].get('degraded_local_runs', 0)},"
          f"degraded_chunks={degrade['faults'].get('degraded_chunks', 0)}",
          flush=True)
    print(f"stap_chaos.written,{out_path}", flush=True)
    return doc


def main():
    import sys

    from repro.core.jaxcache import enable_compile_cache

    enable_compile_cache()

    if "--hetero" in sys.argv:
        run_hetero(smoke="--smoke" in sys.argv)
    elif "--pallas" in sys.argv:
        run_pallas(smoke="--smoke" in sys.argv)
    elif "--chaos" in sys.argv:
        run_chaos(smoke="--smoke" in sys.argv)
    elif "--distrib" in sys.argv:
        run_distrib(smoke="--smoke" in sys.argv)
    else:
        run()


if __name__ == "__main__":
    main()
