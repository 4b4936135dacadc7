"""In-repo Pallas kernels (matmul, flash attention, selective scan).

Each kernel package holds the kernel, a jnp oracle (``ref.py``) and a
dispatching wrapper (``ops.py``); :mod:`repro.kernels.api` is the chunk
surface generated pallas twins call.
"""


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted in this process: compiled
    on a TPU backend, interpreted on any other. The one place this is
    decided; the kernels are written for the TPU (VMEM scratch, Mosaic
    tiling), so no other backend compiles them."""
    import jax

    return jax.default_backend() != "tpu"


def dot_precision(dtype):
    """Precision of a kernel's dot on ``dtype`` operands: f32 at full
    f32 (the TPU's default would take a single bf16 pass), any other
    dtype at the matrix unit's native default. Given explicitly, so an
    ambient ``jax.default_matmul_precision`` cannot push bf16 operands
    to a precision Mosaic refuses."""
    import jax
    import jax.numpy as jnp

    return (jax.lax.Precision.HIGHEST
            if jnp.dtype(dtype) == jnp.float32
            else jax.lax.Precision.DEFAULT)
