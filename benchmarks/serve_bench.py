"""Serving-plane benchmark: open-loop Poisson load against the
multi-tenant cluster engines, written to ``BENCH_serve.json``.

Two flagships, per ROADMAP item #3 ("millions of users", measured):

* **stap** — the adaptive STAP kernel (examples/stap.py) compiled by
  the repo's own pipeline and served through
  :class:`repro.serve.ClusterServeEngine` on a real worker fleet.
  The same Poisson schedule runs twice: ``naive`` (coalescing window
  0 — every request is its own pfor round) and ``coalesced``
  (same-signature requests merge into one stacked pfor). The win the
  row pair measures is round amortization: N requests of k gates
  become one N·k-gate pfor — bigger chunks, one ship/dispatch/gather.

* **lm_decode** — token-by-token LM inference:
  :class:`repro.serve.ClusterLMEngine` (params + KV caches resident in
  a worker's object store) versus the single-process seed
  ``ServeEngine``, same prompts. The cluster row must match the
  single-process token streams **exactly** (``exact_match``) and
  reports TTFT / per-output-token / end-to-end percentiles under the
  open-loop load.

    PYTHONPATH=src python -m benchmarks.serve_bench [--smoke] \
        [--stap-only | --lm-only]
"""

from __future__ import annotations

import json
import time
from typing import Dict, List

import numpy as np

OUT_PATH = "BENCH_serve.json"


# ---------------------------------------------------------------------------
# STAP kernel serving: coalesced vs naive under the same Poisson load
# ---------------------------------------------------------------------------

def run_stap(smoke: bool = False) -> List[Dict]:
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from examples.stap import ALPHA, LOADING, stap_adaptive, stap_seq
    from repro.core.compiler import compile_kernel
    from repro.distrib import ClusterRuntime
    from repro.serve import (AdmissionController, BatchSpec,
                             ClusterServeEngine, TenantQuota, open_loop)

    if smoke:
        gates, k, dof, iters = 8, 12, 12, 40
        requests, workers = 48, 2
    else:
        gates, k, dof, iters = 16, 24, 24, 60
        requests, workers = 96, 2

    rng = np.random.default_rng(7)
    steer = rng.normal(size=dof)
    trains = [rng.normal(size=(gates, k, dof)) for _ in range(requests)]
    snaps = [rng.normal(size=(gates, dof)) for _ in range(requests)]
    expected = []
    for tr, sn in zip(trains, snaps):
        o = np.zeros(gates)
        stap_seq(sn, tr, steer, o, gates, k, dof, iters, ALPHA, LOADING)
        expected.append(o)

    rows: List[Dict] = []
    rt = ClusterRuntime(workers=workers)
    try:
        ck = compile_kernel(stap_adaptive, runtime=rt)
        ck.pfor_config.distribute_threshold = 0   # force the cluster
        batch = BatchSpec(stacked=("snap", "train"), count="numGates",
                          out=("outY",),
                          shared=("steer", "K", "dof", "iters",
                                  "alpha", "loading"))
        # warm calls ship + persist the body blob on the workers, and
        # measure the per-request service time; the open-loop rate is
        # pinned at 3x naive capacity so per-request dispatch is
        # genuinely saturated (an open-loop driver below capacity never
        # queues, and an empty queue has nothing to coalesce). The
        # schedule is cumulative, so even sub-millisecond gaps are
        # honored on average.
        warm = np.zeros(gates)
        t_call = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            ck.call_variant("np", snaps[0], trains[0], steer, warm,
                            gates, k, dof, iters, ALPHA, LOADING)
            t_call = min(t_call, time.perf_counter() - t0)
        rate_rps = min(1500.0, max(30.0, 3.0 / t_call))

        for mode, window in (("naive", 0.0), ("coalesced", 0.01)):
            eng = ClusterServeEngine(
                rt, coalesce_window_s=window, max_batch=16,
                admission=AdmissionController(
                    default=TenantQuota(max_inflight=256),
                    max_queue=1024))
            eng.register("stap", ck, batch=batch)
            outs = [np.zeros(gates) for _ in range(requests)]

            def submit(i, tenant):
                return eng.submit(tenant, "stap",
                                  (snaps[i], trains[i], steer, outs[i],
                                   gates, k, dof, iters, ALPHA,
                                   LOADING))

            res = open_loop(submit, requests=requests,
                            rate_rps=rate_rps, seed=11,
                            tenants=("tenant-a", "tenant-b"))
            eng.close()
            err = max(float(np.abs(o - e).max())
                      for o, e in zip(outs, expected))
            tel = eng.telemetry()
            row = {"flagship": "stap", "mode": mode,
                   "workers": workers, "gates_per_request": gates,
                   "coalesce_window_s": window, "measured": True,
                   "service_ms": round(t_call * 1e3, 3),
                   "max_abs_err": err,
                   "coalesced_batches": tel["coalesced_batches"],
                   "coalesced_requests": tel["coalesced_requests"],
                   "fallthrough_dispatches":
                       tel["fallthrough_dispatches"],
                   **res.as_row()}
            rows.append(row)
            print(f"[serve_bench] stap/{mode}: "
                  f"{row['throughput_rps']:.1f} req/s, "
                  f"e2e p95 {row['e2e_ms'].get('p95')}ms, "
                  f"batches={row['coalesced_batches']}, "
                  f"max|err|={err:.1e}")
            assert err < 1e-8, f"stap serving mismatch ({mode}): {err}"
    finally:
        rt.shutdown()
    return rows


# ---------------------------------------------------------------------------
# LM decode flagship: cluster engine vs single-process, exact match
# ---------------------------------------------------------------------------

def run_lm(smoke: bool = False) -> List[Dict]:
    from repro.configs import get_smoke_config
    from repro.distrib import ClusterRuntime
    from repro.models import transformer as T
    from repro.serve import ClusterLMEngine, open_loop
    from repro.serve.engine import Request, ServeEngine

    requests = 6 if smoke else 16
    max_tokens = 8 if smoke else 16
    n_slots, max_seq, workers = 2, 64, 1
    rate_rps = 20.0

    # spawn the decode worker before this process touches jax: on an
    # accelerator host the first process to initialise jax holds the chip
    rt = ClusterRuntime(workers=workers, start_method="spawn")
    try:
        import jax

        cfg = get_smoke_config("stablelm_3b")
        params, _ = T.init_params(cfg, jax.random.key(0))
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, cfg.vocab, int(rng.integers(4, 12)))
                   for _ in range(requests)]

        # single-process reference (and its own telemetry row)
        ref_eng = ServeEngine(params, cfg, n_slots=n_slots,
                              max_seq=max_seq)
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            ref_eng.add_request(Request(f"req-{i}", p,
                                        max_tokens=max_tokens))
        ref_done = ref_eng.run_until_done()
        ref_wall = time.perf_counter() - t0
        ref = {r.request_id: list(r.generated) for r in ref_done}
        ref_tel = ref_eng.telemetry()
        rows: List[Dict] = [{
            "flagship": "lm_decode", "mode": "single_process",
            "workers": 0, "requests": requests, "measured": True,
            "tokens_generated": ref_tel["tokens_generated"],
            "throughput_tok_s": round(
                ref_tel["tokens_generated"] / ref_wall, 2),
            "ttft_ms": ref_tel["latency"]["ttft_ms"],
            "tpot_ms": ref_tel["latency"]["tpot_ms"],
            "e2e_ms": ref_tel["latency"]["e2e_ms"],
        }]

        eng = ClusterLMEngine(rt, params, cfg, n_slots=n_slots,
                              max_seq=max_seq, trim_every=16)
        # warm the worker's jit cache off the measured clock (the
        # warmup slot decodes alongside early requests; slots are
        # row-independent, so measured token streams are unaffected)
        eng.submit("warmup", prompts[0], max_tokens=2,
                   request_id="warm-0").wait(300.0)

        got: Dict[str, List[int]] = {}

        def submit(i, tenant):
            return eng.submit(tenant, prompts[i],
                              max_tokens=max_tokens,
                              request_id=f"req-{i}")

        res = open_loop(submit, requests=requests, rate_rps=rate_rps,
                        seed=5, tenants=("tenant-a", "tenant-b"),
                        wait_timeout_s=300.0)
        for r in eng.finished:
            if r.request_id.startswith("req-"):
                got[r.request_id] = list(r.generated)
        exact = got == ref
        tel = eng.telemetry()
        eng.close()
        row = {"flagship": "lm_decode", "mode": "cluster",
               "workers": workers, "requests": requests,
               "measured": True, "exact_match": exact,
               "tokens_generated": tel["tokens_generated"],
               "throughput_tok_s": round(
                   tel["tokens_generated"] / max(res.duration_s, 1e-9),
                   2),
               "anchors": tel["anchors"],
               "ttft_ms": tel["latency"]["ttft_ms"],
               "tpot_ms": tel["latency"]["tpot_ms"],
               "per_tenant_tokens": tel["tenants"]["tokens"],
               **res.as_row()}
        rows.append(row)
        print(f"[serve_bench] lm/cluster: exact_match={exact}, "
              f"{row['throughput_rps']:.1f} req/s, "
              f"ttft p50 {row['ttft_ms']['p50']:.1f}ms, "
              f"tpot p50 {row['tpot_ms']['p50']:.1f}ms")
        assert exact, ("cluster LM decode diverged from the "
                       "single-process engine")
    finally:
        rt.shutdown()
    return rows


def main() -> None:
    import sys

    from repro.core.jaxcache import enable_compile_cache

    enable_compile_cache()

    smoke = "--smoke" in sys.argv
    rows: List[Dict] = []
    if "--lm-only" not in sys.argv:
        rows += run_stap(smoke=smoke)
    if "--stap-only" not in sys.argv:
        rows += run_lm(smoke=smoke)

    doc: Dict = {"benchmark": "serve", "smoke": smoke, "rows": rows}
    stap = {r["mode"]: r for r in rows if r["flagship"] == "stap"}
    if {"naive", "coalesced"} <= stap.keys():
        n, c = stap["naive"], stap["coalesced"]
        doc["coalesced_vs_naive"] = {
            "throughput_ratio": round(
                c["throughput_rps"] / max(n["throughput_rps"], 1e-9),
                3),
            "p95_ratio": round(
                c["e2e_ms"]["p95"] / max(n["e2e_ms"]["p95"], 1e-9), 3),
        }
        print(f"[serve_bench] coalesced vs naive: "
              f"{doc['coalesced_vs_naive']}")
    with open(OUT_PATH, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"[serve_bench] wrote {OUT_PATH} ({len(rows)} rows)")


if __name__ == "__main__":
    main()
