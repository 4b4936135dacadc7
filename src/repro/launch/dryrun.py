import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell:
  * build the step function (train_step / prefill / serve_step),
  * auto-shard with the planner (legality → profitability),
  * ``jax.jit(fn, in_shardings=…).lower(**ShapeDtypeStructs).compile()``
    on the production mesh — 512 placeholder host devices stand in for
    the chips; XLA runs the full GSPMD partitioner so sharding mismatches,
    compile-time OOMs and unsupported collectives surface as real errors,
  * record memory_analysis / cost_analysis / per-collective bytes (parsed
    from the compiled HLO) into artifacts/dryrun/results.json — the
    roofline analysis (§Roofline in EXPERIMENTS.md) reads from there.

Usage:
  python -m repro.launch.dryrun --arch stablelm_3b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""

import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_config
from repro.configs.registry import ShapeSpec, cell_is_skipped
from repro.core import planner as planner_mod
from repro.core.cost import TPU_V5E, roofline
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.models.common import ArchConfig
from repro.train import AdamWConfig, init_opt_state, make_train_step
from repro.train.optimizer import MomentState

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Abstract input batch for one cell (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind in ("train", "prefill"):
        batch: Dict[str, Any] = {}
        if cfg.embeds_input:
            batch["embeds"] = sds((B, S, cfg.d_model), jnp.float32)
        else:
            batch["tokens"] = sds((B, S), jnp.int32)
        if shape.kind == "train":
            batch["labels"] = sds((B, S), jnp.int32)
        if cfg.is_encdec:
            batch["src_embeds"] = sds((B, S, cfg.d_model), jnp.float32)
        return batch
    # decode: one new token against a full cache
    return {"tokens": sds((B, 1), jnp.int32)}


def _static_specs(cfg: ArchConfig):
    """Build the specs tree without materializing params."""
    closure: Dict[str, Any] = {}

    def capture():
        params, specs = T.init_params(cfg, jax.random.key(0))
        closure["specs"] = specs
        return params

    jax.eval_shape(capture)
    return closure["specs"]


# ---------------------------------------------------------------------------
# Cell builders
# ---------------------------------------------------------------------------

def build_train_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, plan):
    opt_cfg = AdamWConfig(quantize_moments=cfg.opt_8bit)
    step = make_train_step(cfg, opt_cfg)
    p_shapes = jax.eval_shape(lambda: T.init_params(
        cfg, jax.random.key(0))[0])
    o_shapes = jax.eval_shape(lambda: init_opt_state(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), p_shapes),
        opt_cfg))
    batch = input_specs(cfg, shape)

    p_sh = plan.param_shardings
    repl = NamedSharding(mesh, P())

    def moment_sh(param_sh):
        return MomentState(param_sh, repl)

    o_sh = type(o_shapes)(
        step=repl,
        m=jax.tree.map(lambda s: moment_sh(s), p_sh,
                       is_leaf=lambda x: isinstance(x, NamedSharding)),
        v=jax.tree.map(lambda s: moment_sh(s), p_sh,
                       is_leaf=lambda x: isinstance(x, NamedSharding)),
    )
    b_sh = jax.tree.map(
        lambda s: planner_mod.batch_sharding(
            mesh, plan.strategy, shape.global_batch,
            extra_dims=len(s.shape) - 1),
        batch)
    metrics_sh = {"loss": repl, "grad_norm": repl, "step": repl}
    jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, metrics_sh))
    return jitted, (p_shapes, o_shapes, batch)


def build_prefill_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, plan):
    batch = input_specs(cfg, shape)

    def prefill_fn(params, batch):
        return T.prefill(params, batch, cfg, max_seq=shape.seq_len)

    p_shapes = jax.eval_shape(lambda: T.init_params(
        cfg, jax.random.key(0))[0])
    b_sh = jax.tree.map(
        lambda s: planner_mod.batch_sharding(
            mesh, plan.strategy, shape.global_batch,
            extra_dims=len(s.shape) - 1),
        batch)
    jitted = jax.jit(prefill_fn, in_shardings=(plan.param_shardings, b_sh))
    return jitted, (p_shapes, batch)


def build_decode_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, plan):
    B, S = shape.global_batch, shape.seq_len
    cross = S if cfg.is_encdec else 0

    def serve_step(params, tokens, caches):
        return T.decode_step(params, tokens, caches, cfg)

    p_shapes = jax.eval_shape(lambda: T.init_params(
        cfg, jax.random.key(0))[0])
    cache_shapes = jax.eval_shape(
        lambda: T.init_caches(cfg, B, S, cross_len=cross,
                              uniform_index=True))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    c_sh = jax.tree.map(
        lambda s: planner_mod.cache_sharding(mesh, plan.strategy, cfg, B,
                                             tuple(s.shape)),
        cache_shapes)
    t_sh = planner_mod.batch_sharding(mesh, plan.strategy, B, extra_dims=1)
    logits_sh = planner_mod.batch_sharding(mesh, plan.strategy, B,
                                           extra_dims=1)
    jitted = jax.jit(serve_step,
                     in_shardings=(plan.param_shardings, t_sh, c_sh),
                     out_shardings=(logits_sh, c_sh))
    return jitted, (p_shapes, tok, cache_shapes)


# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8\w*|s64|s32|s16|s8|u64|u32|"
                       r"u16|u8|pred|c64|c128)\[([\d,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8,
                "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4,
                "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    per_kind: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        lhs, rhs = ls.split("=", 1)
        rhs = rhs.strip()
        for kind in _COLLECTIVES:
            # match op name at the start of the rhs expression:
            #   bf16[...]{...} all-gather(...)
            m = re.match(r"^(\([^)]*\)|[\w\[\],{}:#*\s]*?)\s*"
                         + kind + r"(-start|-done)?\(", rhs)
            if m:
                if m.group(2) == "-done":
                    break  # counted at -start
                # result shape(s) of the collective (output-size convention)
                header = rhs.split(kind)[0]
                per_kind[kind] += _shape_bytes(header)
                counts[kind] += 1
                break
    total = sum(per_kind.values())
    return {"total_bytes": total, "per_kind_bytes": per_kind,
            "counts": counts}


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             cfg_override: Optional[ArchConfig] = None,
             verbose: bool = True) -> Dict[str, Any]:
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    skip = cell_is_skipped(cfg, shape_name)
    if skip:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": skip}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.perf_counter()
    p_shapes = jax.eval_shape(lambda: T.init_params(
        cfg, jax.random.key(0))[0])
    specs = _static_specs(cfg)
    plan = planner_mod.plan(cfg, specs, p_shapes, mesh,
                            seq=shape.seq_len, batch=shape.global_batch,
                            kind=shape.kind)
    import dataclasses as _dc

    rows = shape.global_batch
    if shape.kind == "train":
        rows = shape.global_batch // max(1, plan.estimate.microbatch)
    # anchor activations on the planner's effective DP axes
    ax: tuple = ()
    for i in range(len(plan.strategy.batch_axes), 0, -1):
        cand = plan.strategy.batch_axes[:i]
        if rows % planner_mod._mesh_size(mesh, cand) == 0:
            ax = cand
            break
    moe_ax = cap_ax = None
    if cfg.n_experts:
        from repro.models.moe import padded_experts

        e_pad = padded_experts(cfg, 16)
        f = cfg.expert_d_ff or cfg.d_ff
        spec = planner_mod.resolve_leaf_spec(
            (e_pad, cfg.d_model, f), ("experts", "embed", "mlp"),
            plan.strategy, mesh)
        if spec[0] is not None:
            moe_ax = (spec[0],) if isinstance(spec[0], str) \
                else tuple(spec[0])
            # capacity dim covers the mesh axes experts cannot
            cap_ax = tuple(a for a in mesh.axis_names
                           if a not in moe_ax) or None
    cfg = _dc.replace(cfg, microbatch=plan.estimate.microbatch
                      if shape.kind == "train" else cfg.microbatch,
                      act_batch_axes=ax or None,
                      moe_expert_axes=moe_ax,
                      moe_capacity_axes=cap_ax)
    if verbose:
        print(f"[{arch} × {shape_name} × "
              f"{'multi' if multi_pod else 'single'}] mb={cfg.microbatch} "
              f"{plan.describe()}", flush=True)

    if shape.kind == "train":
        jitted, args = build_train_cell(cfg, shape, mesh, plan)
    elif shape.kind == "prefill":
        jitted, args = build_prefill_cell(cfg, shape, mesh, plan)
    else:
        jitted, args = build_decode_cell(cfg, shape, mesh, plan)

    with mesh:
        lowered = jitted.lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "chips": mesh.size,
        "status": "ok",
        "strategy": plan.strategy.name,
        "microbatch": cfg.microbatch,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "planner_estimate": {
            "hbm_gib_per_chip": plan.estimate.hbm_bytes_per_chip / 2**30,
            "compute_s": plan.estimate.compute_s,
            "memory_s": plan.estimate.memory_s,
            "collective_s": plan.estimate.collective_s,
        },
    }

    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        result["cost_analysis"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0)),
        }
    except Exception as exc:  # pragma: no cover
        result["cost_analysis"] = {"error": str(exc)}

    try:
        ma = compiled.memory_analysis()
        mem = {}
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            if hasattr(ma, attr):
                mem[attr] = int(getattr(ma, attr))
        result["memory_analysis"] = mem
    except Exception as exc:  # pragma: no cover
        result["memory_analysis"] = {"error": str(exc)}

    try:
        from repro.launch import hlo_analysis

        corrected = hlo_analysis.analyze_compiled(compiled)
        result["hlo_corrected"] = corrected
    except Exception as exc:  # pragma: no cover
        corrected = {}
        result["hlo_corrected"] = {"error": str(exc)}

    # roofline terms (per §Roofline; single-pod is the reported table).
    # FLOPs/bytes/collective are trip-count-corrected from the optimized
    # HLO (launch/hlo_analysis.py) — raw cost_analysis() counts each
    # while body once and is kept only for reference.
    n_active = cfg.active_param_count()
    tokens = (shape.global_batch * shape.seq_len
              if shape.kind != "decode" else shape.global_batch)
    model_flops = (6.0 if shape.kind == "train" else 2.0) * n_active \
        * tokens
    # the optimized module is the per-device SPMD program → corrected
    # numbers are PER-CHIP; roofline terms divide by 1 chip.
    hlo_flops = corrected.get("flops_corrected", 0.0) or 0.0
    hlo_bytes = corrected.get("memory_bytes_corrected", 0.0) or 0.0
    coll_bytes = corrected.get("collective_bytes_corrected", 0.0) or 0.0
    rt = roofline(hlo_flops, hlo_bytes, coll_bytes, 1, TPU_V5E)
    global_hlo_flops = hlo_flops * mesh.size
    result["roofline"] = {
        "compute_s": rt.compute_s,
        "memory_s": rt.memory_s,
        "collective_s": rt.collective_s,
        "dominant": rt.dominant,
        "model_flops": model_flops,
        "hlo_flops_global": global_hlo_flops,
        "useful_flops_ratio": (model_flops / global_hlo_flops
                               if global_hlo_flops else None),
    }
    if verbose:
        print(f"  ok: compile={t_compile:.1f}s flops={hlo_flops:.3e} "
              f"bytes={hlo_bytes:.3e} coll={coll_bytes:.3e} "
              f"dominant={rt.dominant}", flush=True)
    return result


# ---------------------------------------------------------------------------
# Sweep + cache
# ---------------------------------------------------------------------------

def _results_path() -> str:
    os.makedirs(ART_DIR, exist_ok=True)
    return os.path.join(ART_DIR, "results.json")


def load_results() -> Dict[str, Any]:
    path = _results_path()
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def save_results(res: Dict[str, Any]) -> None:
    with open(_results_path(), "w") as f:
        json.dump(res, f, indent=1)


def cell_key(arch: str, shape: str, multi_pod: bool) -> str:
    return f"{arch}|{shape}|{'multi' if multi_pod else 'single'}"


def sweep(archs, shapes, meshes, force=False) -> None:
    results = load_results()
    for multi_pod in meshes:
        for arch in archs:
            for shape in shapes:
                key = cell_key(arch, shape, multi_pod)
                prev = results.get(key)
                if prev and not force and prev.get("status") in (
                        "ok", "skipped"):
                    continue
                try:
                    res = run_cell(arch, shape, multi_pod=multi_pod)
                except Exception as exc:
                    res = {"arch": arch, "shape": shape,
                           "mesh": "multi" if multi_pod else "single",
                           "status": "error", "error": str(exc)[:2000],
                           "traceback":
                               traceback.format_exc()[-4000:]}
                    print(f"[{key}] ERROR: {exc}", flush=True)
                results[key] = res
                save_results(results)


def main() -> None:
    from repro.core.jaxcache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--set", dest="overrides", action="append",
                    default=[], metavar="KEY=VALUE",
                    help="config override for hillclimb iterations "
                         "(e.g. --set microbatch=8)")
    ap.add_argument("--tag", default=None,
                    help="store result under <cell>#<tag> (keeps the "
                         "baseline row)")
    args = ap.parse_args()

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    if args.all:
        sweep(ARCHS, list(SHAPES), meshes, force=args.force)
        return
    assert args.arch and args.shape, "--arch and --shape (or --all)"
    cfg_override = None
    if args.overrides:
        import dataclasses as _dc

        cfg_override = get_config(args.arch)
        kv = {}
        for ov in args.overrides:
            k, v = ov.split("=", 1)
            cur = getattr(cfg_override, k)
            if isinstance(cur, bool):
                v = v.lower() in ("1", "true", "yes")
            elif isinstance(cur, int):
                v = int(v)
            elif isinstance(cur, float):
                v = float(v)
            kv[k] = v
        cfg_override = _dc.replace(cfg_override, **kv)
    res = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   cfg_override=cfg_override)
    if args.overrides:
        res["overrides"] = args.overrides
    key = cell_key(args.arch, args.shape, args.multi_pod)
    if args.tag:
        key = f"{key}#{args.tag}"
    results = load_results()
    results[key] = res
    save_results(results)
    print(json.dumps({k: res.get(k) for k in
                      ("strategy", "microbatch", "roofline")}, indent=1))


if __name__ == "__main__":
    main()
