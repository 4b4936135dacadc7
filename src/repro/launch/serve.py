"""Serving driver: batched continuous-batching engine over a model.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm_3b \
        --smoke --requests 8 --max-tokens 12

Loads (or initializes) a model, spins up the ServeEngine (fixed-slot KV
cache, per-slot positions, greedy decode), feeds a synthetic request
stream with staggered arrivals, and reports latency/throughput stats.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine


def main(argv=None) -> dict:
    from repro.core.jaxcache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--max-tokens", type=int, default=12)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke \
        else get_config(args.arch)
    params, _ = T.init_params(cfg, jax.random.key(0))
    eng = ServeEngine(params, cfg, n_slots=args.slots,
                      max_seq=args.max_seq)

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(args.requests):
        plen = int(rng.integers(4, 16))
        eng.add_request(Request(
            f"req-{i}", rng.integers(0, cfg.vocab, plen),
            max_tokens=args.max_tokens))
        eng.step()  # staggered arrivals exercise continuous batching
    done = eng.run_until_done()
    wall = time.perf_counter() - t0

    gen_tokens = sum(len(r.generated) for r in done)
    ttfts = [r.first_token_s - r.submitted_s for r in done]
    lats = [r.finished_s - r.submitted_s for r in done]
    stats = {
        "requests": len(done),
        "tokens_generated": gen_tokens,
        "throughput_tok_s": gen_tokens / wall,
        "ttft_p50_s": float(np.median(ttfts)),
        "latency_p50_s": float(np.median(lats)),
    }
    print(f"[serve] {cfg.name}: {stats}")
    return stats


if __name__ == "__main__":
    main()
