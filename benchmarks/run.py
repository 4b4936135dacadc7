"""Benchmark driver — one function per paper table/figure.

  polybench   → paper Table 4 + Fig. 8 (15 kernels, 4 variants)
  fusion      → fused vs unfused timings per kernel/backend
                (machine-readable BENCH_fusion.json)
  stap        → paper Figs. 9-10 (throughput + scaling; cluster dimension
                simulated, labeled)
  kernels     → Pallas kernel parity vs jnp oracles (interpret mode)
  dryrun      → roofline table per (arch × shape × mesh) from artifacts

Prints ``name,value,derived`` CSV lines.
"""

from __future__ import annotations

import time


def _section(title):
    print(f"\n### {title}", flush=True)


def bench_kernels():
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.matmul.ops import matmul
    from repro.kernels.matmul.ref import matmul_ref
    from repro.kernels.mamba_scan.ops import mamba_scan
    from repro.kernels.mamba_scan.ref import mamba_scan_ref

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)
    got = matmul(x, y, force_pallas=True, interpret=True, bm=128, bn=128,
                 bk=256)
    err = float(jnp.abs(got - matmul_ref(x, y)).max())
    print(f"kernels.matmul_interpret,parity_maxerr={err:.2e}")

    q = jnp.asarray(rng.normal(size=(1, 128, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
    got = flash_attention(q, k, v, force_pallas=True, interpret=True,
                          bq=64, bk=64)
    err = float(jnp.abs(got - attention_ref(q, k, v)).max())
    print(f"kernels.flash_attention_interpret,parity_maxerr={err:.2e}")

    B, L, I, N = 1, 64, 16, 4
    xs = jnp.asarray(rng.normal(size=(B, L, I)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(B, L, I))) * 0.1,
                     jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, L, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, L, N)), jnp.float32)
    a = jnp.asarray(np.log(np.abs(rng.normal(size=(I, N))) + 0.5),
                    jnp.float32)
    d = jnp.asarray(rng.normal(size=(I,)), jnp.float32)
    got = mamba_scan(xs, dt, Bm, Cm, a, d, chunk=16, force_pallas=True,
                     interpret=True)
    err = float(jnp.abs(got - mamba_scan_ref(xs, dt, Bm, Cm, a, d)).max())
    print(f"kernels.mamba_scan_interpret,parity_maxerr={err:.2e}")


def main() -> None:
    from repro.core.jaxcache import enable_compile_cache

    enable_compile_cache()
    t0 = time.perf_counter()
    _section("polybench (paper Table 4 / Fig 8)")
    from . import polybench

    polybench.run(n=192, list_n=32)

    _section("fusion: fused vs unfused (BENCH_fusion.json)")
    polybench.run_fusion()

    _section("stap (paper Figs 9-10)")
    from . import stap

    stap.run()

    _section("stap distributed: cluster runtime (BENCH_distrib.json)")
    stap.run_distrib()

    _section("pallas kernels (interpret-mode parity)")
    bench_kernels()

    _section("dryrun roofline table (EXPERIMENTS.md §Roofline)")
    from . import dryrun_table

    dryrun_table.main()

    print(f"\nbenchmarks.total_s,{time.perf_counter() - t0:.1f}")


if __name__ == "__main__":
    main()
