"""PolyBench benchmark — reproduces the paper's Table 4 + Fig. 8
methodology on this host:

  variants per kernel:
    list_default   — original Python loops over lists (paper "List Default")
    numpy          — original NumPy version (paper "NumPy" baseline)
    automphc_cpu   — our compiler's optimized-NumPy variant (paper
                     "AutoMPHC opt-CPU")
    automphc_accel — our compiler's JAX variant where feasible (paper
                     "AutoMPHC opt-GPU": the NumPy→CuPy conversion,
                     retargeted at XLA)

Reports seconds and GFLOP/s per variant. List-default timings use a
reduced problem size with measured-time extrapolation (n³ kernels at
paper-scale list sizes take minutes in pure Python; the paper's own Table
4 shows 150-350 s — we scale instead of burning the suite budget) —
marked with '*' in the output.
"""

from __future__ import annotations

import json
import platform
import time
from typing import Dict, List, Optional

import numpy as np

from .fusion_chains import CHAINS
from .polybench_kernels import KERNELS, clone_args, to_lists


def _time(fn, *args, repeat=3, min_time=0.01) -> float:
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn(*args)
        best = min(best, time.perf_counter() - t0)
        if best > 5.0:
            break
    return best


def run(n: int = 256, list_n: int = 48, kernels: List[str] = None,
        csv: bool = True) -> List[Dict]:
    from repro.core.compiler import compile_kernel

    rows = []
    names = kernels or list(KERNELS)
    for name in names:
        k = KERNELS[name]
        rng = np.random.default_rng(11)

        # -- list default (reduced size, scaled) -------------------------
        args_small, _ = k["make_args"](list_n, rng)
        la = to_lists(clone_args(args_small))
        t_list_small = _time(k["list"], *la, repeat=1)
        scale = k["flops"](n) / max(k["flops"](list_n), 1.0)
        t_list = t_list_small * scale

        # -- numpy baseline ----------------------------------------------
        args, _ = k["make_args"](n, rng)
        t_numpy = _time(k["np"], *clone_args(args))

        # -- AutoMPHC variants -------------------------------------------
        ck = compile_kernel(k["np"])
        t_cpu = _time(lambda *a: ck.call_variant("np", *a),
                      *clone_args(args))
        t_accel = None
        if "jnp" in ck.variants:
            ck.call_variant("jnp", *clone_args(args))  # compile warmup
            t_accel = _time(lambda *a: ck.call_variant("jnp", *a),
                            *clone_args(args))

        gf = k["flops"](n) / 1e9
        row = {
            "kernel": name,
            "list_default_s*": t_list,
            "numpy_s": t_numpy,
            "automphc_cpu_s": t_cpu,
            "automphc_accel_s": t_accel,
            "numpy_gflops": gf / t_numpy if t_numpy else None,
            "automphc_cpu_gflops": gf / t_cpu if t_cpu else None,
            "automphc_accel_gflops": (gf / t_accel
                                      if t_accel else None),
            "speedup_cpu_vs_numpy": t_numpy / t_cpu if t_cpu else None,
            "speedup_cpu_vs_list": t_list / t_cpu if t_cpu else None,
        }
        rows.append(row)
        if csv:
            acc = f"{t_accel:.4g}" if t_accel else "n/a"
            print(f"polybench.{name},{t_list:.4g}*,{t_numpy:.4g},"
                  f"{t_cpu:.4g},{acc},"
                  f"x{row['speedup_cpu_vs_numpy']:.2f}_vs_numpy",
                  flush=True)
    return rows


# ---------------------------------------------------------------------------
# Fusion benchmark (BENCH_fusion.json): fused vs unfused, same backend
# ---------------------------------------------------------------------------

# (kernel, style, backend, n): producer–consumer chains isolate the fusion
# patterns at the backend where each pattern pays — contraction of local
# intermediates on the in-place np backend, statement folding on the
# functional jnp backend (where every unfused statement costs a full
# `.at[].set` materialization). PolyBench list styles ride on jnp, where
# the fused form is exactly the hand-written NumPy statement.
FUSION_BENCH = [
    ("smooth", "np", "np", 1200),
    ("scaled_sq", "np", "np", 1200),
    ("doitgen_local", "np", "np", 256),
    ("elem_chain", "np", "jnp", 1000),
    ("vec_chain", "np", "jnp", 1000),
    ("gemm", "list", "jnp", 500),
    ("2mm", "list", "jnp", 400),
    ("3mm", "list", "jnp", 400),
    ("atax", "list", "jnp", 1500),
    ("bicg", "list", "jnp", 1500),
    ("gesummv", "list", "jnp", 1000),
    ("2mm", "list", "np", 400),
    ("atax", "list", "np", 1500),
]


def _registry(name):
    return CHAINS[name] if name in CHAINS else KERNELS[name]


def run_fusion(n: Optional[int] = None, check_n: int = 16, repeat: int = 5,
               out_path: Optional[str] = "BENCH_fusion.json",
               kernels: Optional[List[str]] = None,
               csv: bool = True) -> List[Dict]:
    """Time each kernel with the fusion pass on vs off (same backend,
    identical pipeline otherwise) and write BENCH_fusion.json.

    Numerical agreement between the two variants and the trusted
    reference is asserted at ``check_n`` before anything is timed.
    ``n`` overrides every row's problem size (smoke mode)."""
    from repro.core.compiler import compile_kernel

    rows: List[Dict] = []
    for name, style, backend, row_n in FUSION_BENCH:
        if kernels and name not in kernels:
            continue
        bench_n = n or row_n
        k = _registry(name)
        fn = k[style]
        ck_fused = compile_kernel(fn, fuse=True)
        ck_plain = compile_kernel(fn, fuse=False)
        if backend not in ck_fused.variants or \
                backend not in ck_plain.variants:
            continue  # e.g. jax unavailable

        # correctness gate (small shapes, fresh inputs per variant)
        rng = np.random.default_rng(7)
        args, meta = k["make_args"](check_n, rng)
        ref_args = clone_args(args)
        k["ref"](*ref_args)
        for ck in (ck_fused, ck_plain):
            test_args = clone_args(args)
            ck.call_variant(backend, *test_args)
            for oi in meta["out"]:
                np.testing.assert_allclose(
                    np.asarray(test_args[oi], dtype=float),
                    np.asarray(ref_args[oi], dtype=float),
                    atol=1e-8, rtol=1e-8)

        # timing (ndarray args either way: list-style variants asarray
        # their inputs, a no-op here, so both variants pay the same cost)
        rng = np.random.default_rng(11)
        args, _ = k["make_args"](bench_n, rng)
        a_plain, a_fused = clone_args(args), clone_args(args)
        ck_plain.call_variant(backend, *a_plain)   # warmup / jax setup
        ck_fused.call_variant(backend, *a_fused)
        t_plain = _time(lambda *a: ck_plain.call_variant(backend, *a),
                        *a_plain, repeat=repeat)
        t_fused = _time(lambda *a: ck_fused.call_variant(backend, *a),
                        *a_fused, repeat=repeat)
        gen = ck_fused.variants[backend].generated
        meta_f = gen.meta if gen is not None else None
        row = {
            "kernel": name,
            "style": style,
            "backend": backend,
            "n": bench_n,
            "unfused_s": t_plain,
            "fused_s": t_fused,
            "speedup": t_plain / t_fused if t_fused else None,
            "fused_units": getattr(meta_f, "fused_units", 0),
            "contracted_arrays": list(
                getattr(meta_f, "contracted_arrays", [])),
        }
        rows.append(row)
        if csv:
            print(f"fusion.{name}.{backend},{t_plain:.4g},{t_fused:.4g},"
                  f"x{row['speedup']:.2f},fused={row['fused_units']},"
                  f"contracted={len(row['contracted_arrays'])}",
                  flush=True)
    if out_path:
        doc = {
            "benchmark": "fusion",
            "repeat": repeat,
            "host": platform.node(),
            "improved": sum(1 for r in rows if r["speedup"]
                            and r["speedup"] > 1.05),
            "rows": rows,
        }
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=2)
    return rows


def main():
    import argparse

    from repro.core.jaxcache import enable_compile_cache

    enable_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--fusion", action="store_true",
                    help="run the fused-vs-unfused comparison only")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / single repeat (CI)")
    ap.add_argument("-n", type=int, default=None)
    ap.add_argument("--out", default="BENCH_fusion.json")
    opts = ap.parse_args()
    if opts.fusion:
        n = opts.n or (48 if opts.smoke else None)
        run_fusion(n=n, repeat=1 if opts.smoke else 5, out_path=opts.out)
        return
    print("kernel,list_default_s*,numpy_s,automphc_cpu_s,"
          "automphc_accel_s,speedup")
    run(n=opts.n or 256)


if __name__ == "__main__":
    main()
