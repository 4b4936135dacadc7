"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm_3b \
        --smoke --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

Wires every substrate together on the local device(s): config → planner
(auto-sharding on the host mesh) → data pipeline (host-sharded, prefetch)
→ jit'd train step (grad accumulation, remat, optional int8 grad
compression, AdamW w/ optional 8-bit moments) → async checkpointing with
resume-on-restart. The production path is the same code under the
(16, 16)/(2, 16, 16) meshes exercised by dryrun.py.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt as C
from repro.configs import get_config, get_smoke_config
from repro.core import planner as planner_mod
from repro.data.pipeline import DataConfig, make_pipeline
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.train import AdamWConfig, init_opt_state, make_train_step


def main(argv=None) -> dict:
    from repro.core.jaxcache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_3b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--compress", default=None, choices=[None, "int8"])
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke \
        else get_config(args.arch)
    cfg.microbatch = min(cfg.microbatch, max(1, args.batch // 2)) or 1
    mesh = make_host_mesh()

    # --- planner: auto-sharding on whatever mesh we actually have -------
    p_shapes = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0))[0])
    holder = {}

    def cap():
        params, specs = T.init_params(cfg, jax.random.key(0))
        holder["specs"] = specs
        return params

    jax.eval_shape(cap)
    plan = planner_mod.plan(cfg, holder["specs"], p_shapes, mesh,
                            seq=args.seq, batch=args.batch, kind="train")
    print(f"[train] {cfg.name}: {plan.describe()}", flush=True)

    opt_cfg = AdamWConfig(lr=args.lr, quantize_moments=cfg.opt_8bit)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg,
                                      compress=args.compress))

    # --- init or resume ---------------------------------------------------
    start_step = 0
    with mesh:
        params, _ = T.init_params(cfg, jax.random.key(0))
        opt = init_opt_state(params, opt_cfg)
        if args.ckpt_dir:
            last = C.latest_step(args.ckpt_dir)
            if last is not None:
                got, extra = C.restore(args.ckpt_dir, last,
                                       {"params": params, "opt": opt})
                params, opt = got["params"], got["opt"]
                start_step = int(extra.get("data_step", last))
                print(f"[train] resumed from step {last}", flush=True)

    data = make_pipeline(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                    global_batch=args.batch,
                                    embeds_dim=cfg.d_model
                                    if cfg.embeds_input else 0,
                                    src_len=args.seq
                                    if cfg.is_encdec else 0,
                                    d_model=cfg.d_model),
                         start_step=start_step)
    ckpt = C.AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None

    losses = []
    t0 = time.perf_counter()
    try:
        with mesh:
            for i in range(start_step, start_step + args.steps):
                batch = {k: jnp.asarray(v)
                         for k, v in data.next().items()}
                params, opt, m = step_fn(params, opt, batch)
                losses.append(float(m["loss"]))
                if (i + 1) % args.log_every == 0:
                    tput = (i + 1 - start_step) * args.batch * args.seq \
                        / (time.perf_counter() - t0)
                    print(f"[train] step {i + 1} loss {losses[-1]:.4f} "
                          f"({tput:.0f} tok/s)", flush=True)
                if ckpt and (i + 1) % args.ckpt_every == 0:
                    ckpt.save_async(i + 1, {"params": params, "opt": opt},
                                    extra={"data_step": i + 1})
    finally:
        data.stop()
        if ckpt:
            ckpt.wait()
    print(f"[train] done: loss {losses[0]:.4f} → {losses[-1]:.4f}")
    return {"losses": losses, "params": params}


if __name__ == "__main__":
    main()
