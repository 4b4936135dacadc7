"""Compiles for a described TPU v5e, at the widths the chip runs, in f32.

Nothing here runs: each test lowers a program of the device path and
has the TPU compiler, which is installed with JAX, compile it for a chip
that is described, not attached. That catches what interpret mode never
checks (block tiling, Mosaic-unsupported ops, int64 index maps under
x64). The Pallas programs must contain their kernel
(``tpu_custom_call``).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import backends
from repro.core.compiler import compile_kernel
from repro.distrib import accel
from repro.kernels import api
from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd
from repro.kernels.mamba_scan import ops as scan_ops
from repro.kernels.matmul import ops as matmul_ops


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile
    cache off (a compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    """Compile as a device worker runs it: at the device bodies' matmul
    precision."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    with backends.device_precision():
        return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_matmul_compiles(one_chip, dtype):
    n = 2048
    c = _compile(lambda x, y: matmul_ops.matmul(x, y, force_pallas=True,
                                                interpret=False),
                 ((n, n), dtype), ((n, n), dtype), sharding=one_chip)
    assert _has_kernel(c)


@pytest.mark.parametrize("rows", [2048, 819], ids=["aligned", "chunk"])
def test_flash_attention_compiles(one_chip, rows):
    """The kernel at (1, 2048, 128), and the chunk surface on a ragged
    row block as the cluster's sharder cuts it."""
    t, d = 2048, 128
    if rows == 2048:
        c = _compile(lambda q, k, v: flash_attention_bhsd(
            q, k, v, causal=False, interpret=False),
            ((1, rows, d), jnp.float32), ((1, t, d), jnp.float32),
            ((1, t, d), jnp.float32), sharding=one_chip)
    else:
        c = _compile(lambda q, k, v: api.attention_block(
            q, k, v, interpret=False),
            ((rows, d), jnp.float32), ((t, d), jnp.float32),
            ((t, d), jnp.float32), sharding=one_chip)
    assert _has_kernel(c)


@pytest.mark.parametrize("rows", [1024, 819], ids=["aligned", "chunk"])
def test_scan_compiles(one_chip, rows):
    """The selective scan at (1, 2048, 1024) with N=16, and the chunk
    surface's first-order recurrence on a ragged row block."""
    f32 = jnp.float32
    if rows == 1024:
        b, length, n = 1, 2048, 16
        c = _compile(lambda x, dt, bm, cm, a, dskip: scan_ops.mamba_scan(
            x, dt, bm, cm, a, dskip, force_pallas=True, interpret=False),
            ((b, length, rows), f32), ((b, length, rows), f32),
            ((b, length, n), f32), ((b, length, n), f32),
            ((rows, n), f32), ((rows,), f32), sharding=one_chip)
    else:
        c = _compile(lambda x: api.scan_block(x, 0.9, interpret=False),
                     ((rows, 1000), f32), sharding=one_chip)
    assert _has_kernel(c)


def test_jnp_twin_pfor_jit_step_compiles(one_chip):
    """The compiled pfor step of adaptive STAP's jnp twin, at the widths
    of the chip smoke (a 1024-gate bucket, K=256, DOF=128), as a device
    worker traces it (device matmul precision)."""
    from benchmarks import chip_kernels as K

    gates, k, dof, bucket = 2, 256, 128, 1024
    captured = {}

    def capture(iter_fn, lo, hi, arrays, write_pos):
        captured["step"] = (iter_fn, arrays)
        return True

    class CaptureRT:
        def pfor_shards(self, body, lo, hi, tile, **kw):
            twin = body.__jnp__
            twin.__globals__["__pfor_jit"] = capture
            twin(lo, hi)

        def distribute_profitable(self, *a, **kw):
            return True

    ck = compile_kernel(K.stap_adaptive, runtime=CaptureRT())
    ck.pfor_config.distribute_threshold = 0
    f32 = np.float32
    ck.call_variant("np", np.zeros((gates, dof), f32),
                    np.zeros((gates, k, dof), f32), np.zeros(dof, f32),
                    np.zeros(gates, f32), gates, k, dof, 800, 0.15, 2.0)
    iter_fn, arrays = captured["step"]
    # sliced arrays arrive padded to the bucket; broadcast ones whole
    shapes = [((bucket,) + a.shape[1:] if a.shape[0] == gates else a.shape,
               a.dtype) for a in map(np.asarray, arrays)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in [((bucket,), np.int64), ((len(arrays),), np.int64)]
            + shapes]
    with backends.device_precision():
        c = accel.vmapped(iter_fn).lower(*args).compile()
    assert c.memory_analysis() is not None


@pytest.mark.parametrize("tokens", [16, 512], ids=["decode", "chunk"])
def test_jamba_expert_layer_compiles(one_chip, tokens):
    """Jamba2-Mini's dropless expert layer at its published widths, 8 of
    16 experts held, in bf16: the grouped matmuls become the chip's
    kernel, and the layer's temporaries stay small beside the 2.8 GB of
    expert weights it reads."""
    from repro.configs.jamba2_mini import ep_config
    from repro.models import moe

    cfg = ep_config()
    d, f, e = cfg.d_model, cfg.expert_d_ff, 8
    bf16 = jnp.bfloat16
    shapes = [((1, tokens, d), bf16), ((d, 16), jnp.float32),
              ((e, d, f), bf16), ((e, d, f), bf16), ((e, f, d), bf16),
              ((d,), bf16)]
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in shapes]

    def layer(x, router, wi, wg, wo, ln):
        p = {"router": router, "wi": wi, "wg": wg, "wo": wo, "ln": ln}
        return moe.apply_moe(p, x, cfg)

    c = jax.jit(layer).lower(*args).compile()
    assert _has_kernel(c)
    assert c.memory_analysis().temp_size_in_bytes < 0.2e9


def test_jamba_mamba_decode_compiles(one_chip):
    """One decode token of the published Mamba-1 mixer for 16 slots,
    with its conv and recurrent state, in bf16 with the scan in f32."""
    from repro.configs.jamba2_mini import ep_config
    from repro.models import ssm

    cfg = ep_config()
    p_shapes = jax.eval_shape(
        lambda: ssm.init_mamba(ssm.KeyGen(jax.random.key(0)), cfg)[0])
    st_shapes = jax.eval_shape(lambda: ssm.init_mamba_state(cfg, 16))
    on = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip)
    x = jax.ShapeDtypeStruct((16, 1, cfg.d_model), jnp.float32,
                             sharding=one_chip)
    c = jax.jit(lambda p, x, st: ssm.apply_mamba(p, x, cfg, st)).lower(
        jax.tree.map(on, p_shapes), x, jax.tree.map(on, st_shapes)).compile()
    assert c.memory_analysis().temp_size_in_bytes < 0.1e9


@pytest.mark.parametrize("arch", ["jamba2_mini", "olmoe_1b_7b",
                                  "qwen1_5_110b", "llava_next_mistral_7b"])
def test_decode_attention_compiles(one_chip, monkeypatch, arch):
    """The per-slot decode attention through the kernel, at an arch's
    published heads, 16 slots of 9216 positions in bf16 (for
    jamba2_mini the benchmark cell's widths): the kernel is there, and
    it reads the caches as they lie (no temporaries a cache's size)."""
    from repro.configs import get_config
    from repro.kernels.decode_attention import ops as decode_ops

    monkeypatch.setattr(decode_ops, "interpret_mode", lambda: False)
    cfg = get_config(arch)
    b, s, bf16 = 16, 9216, jnp.bfloat16
    kv = ((b, s, cfg.kv_heads, cfg.head_dim), bf16)
    assert decode_ops.engaged(jax.ShapeDtypeStruct(*kv))
    window = cfg.layer_window(cfg.attn_idx)
    c = _compile(lambda q, k, v, idx, valid: decode_ops.decode_attention(
        q, k, v, idx, valid, window=window, softcap=cfg.attn_softcap),
        ((b, 1, cfg.n_heads, cfg.head_dim), bf16), kv, kv,
        ((b,), jnp.int32), ((b,), jnp.int32), sharding=one_chip)
    assert _has_kernel(c)
    assert c.memory_analysis().temp_size_in_bytes < 1e6


def test_jamba_decode_step_reads_kv_through_the_kernel(one_chip,
                                                       monkeypatch):
    """The benchmark cell's decode step, one period of Jamba2-Mini at
    published widths over 16 slots of 9216 positions, caches donated:
    its attention is the kernel, not a loop over 1024-position slices
    of the whole K and V caches, and its temporaries stay small."""
    from repro.configs.jamba2_mini import ep_config
    from repro.kernels.decode_attention import ops as decode_ops
    from repro.models import transformer as T
    from repro.serve.engine import lm_steps

    monkeypatch.setattr(decode_ops, "interpret_mode", lambda: False)
    cfg = ep_config()
    slots, max_seq = 16, 9216
    on = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip)
    params = jax.tree.map(on, jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0))[0]))
    caches = jax.tree.map(on, jax.eval_shape(
        lambda: T.init_caches(cfg, slots, max_seq)))
    toks = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    c = lm_steps(cfg, max_seq).decode.lower(
        params, toks, valid, caches).compile()
    text = c.as_text()
    assert _has_kernel(c)
    assert "bf16[16,1024,8,128]" not in text
    assert c.memory_analysis().temp_size_in_bytes < 0.1e9
