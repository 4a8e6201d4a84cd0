"""How a pallas kernel of this package runs: decided in one place.

On a TPU backend every kernel compiles through Mosaic; anywhere else
(the CPU tests) it runs in the pallas interpreter. Neither function
catches anything: a backend that fails to initialize, or a toolchain
that refuses the compiler parameters, is an error the caller must see —
an interpreted kernel on a chip would pass every test and measure
nothing.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

# v5e has 128MB of VMEM; the compiler's default 16MB scoped budget
# rejects the fastest flash tiling (256, 1024) by ~0.4MB when the kernel
# sits inside the full train program
VMEM_LIMIT = 64 * 1024 * 1024


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU (kernels compile);
    False means interpret mode, which only CPU tests may use."""
    return jax.default_backend() == "tpu"


def compiler_params(dims):
    return pltpu.CompilerParams(dimension_semantics=dims,
                                vmem_limit_bytes=VMEM_LIMIT)
