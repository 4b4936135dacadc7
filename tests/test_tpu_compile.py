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
