"""Warm vs cold compile benchmark for the persistent variant cache.

Compiles every PolyBench kernel (np style) twice: cold (empty cache dir,
full parse → SCoP → dependence → schedule → codegen) and warm (a fresh
``VariantCache`` over the same dir, simulating a process restart — the
dispatcher is rebuilt from stored source). Reports per-kernel and total
times plus the aggregate speedup, and verifies via telemetry that every
warm compile actually skipped codegen.

Run:  PYTHONPATH=src python benchmarks/warm_cold_compile.py
"""

import argparse
import shutil
import tempfile
import time

from benchmarks.polybench_kernels import KERNELS
from repro.core.compiler import compile_kernel
from repro.profiler import VariantCache


def bench(repeat: int = 3):
    cache_dir = tempfile.mkdtemp(prefix="automphc-bench-cache-")
    rows = []
    try:
        for name in sorted(KERNELS):
            fn = KERNELS[name]["np"]

            cold_cache = VariantCache(cache_dir)
            t0 = time.perf_counter()
            compile_kernel(fn, cache=cold_cache)
            cold_s = time.perf_counter() - t0
            assert cold_cache.stats.puts == 1, name

            warm_best = float("inf")
            skipped = 0
            for _ in range(repeat):
                warm_cache = VariantCache(cache_dir)  # fresh = restart
                t0 = time.perf_counter()
                compile_kernel(fn, cache=warm_cache)
                warm_best = min(warm_best, time.perf_counter() - t0)
                skipped += warm_cache.stats.codegen_skipped
            assert skipped == repeat, \
                f"{name}: warm compile did not skip codegen"
            rows.append((name, cold_s, warm_best))
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)

    print(f"{'kernel':<16} {'cold (ms)':>10} {'warm (ms)':>10} "
          f"{'speedup':>8}")
    print("-" * 48)
    tot_cold = tot_warm = 0.0
    for name, cold_s, warm_s in rows:
        tot_cold += cold_s
        tot_warm += warm_s
        print(f"{name:<16} {cold_s*1e3:>10.2f} {warm_s*1e3:>10.2f} "
              f"{cold_s/warm_s:>7.1f}x")
    print("-" * 48)
    print(f"{'TOTAL':<16} {tot_cold*1e3:>10.2f} {tot_warm*1e3:>10.2f} "
          f"{tot_cold/tot_warm:>7.1f}x")
    print(f"\nall {len(rows)} warm compiles skipped codegen "
          f"(verified by cache telemetry)")
    return rows


if __name__ == "__main__":
    from repro.core.jaxcache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeat", type=int, default=3,
                    help="warm-compile repetitions (best-of)")
    args = ap.parse_args()
    bench(repeat=args.repeat)
