"""Hand-written pallas TPU kernels for ops XLA does not fuse well.

The TPU analog of the reference's hand-tuned CUDA/xbyak kernels
(/root/reference/paddle/fluid/operators/jit/gen/jitcode.h:66,
operators/fused/): where the reference emits x86/SASS for hot loops, the
TPU build emits Mosaic via pallas. `backend.on_tpu()` is the one
predicate: on a TPU every kernel compiles, elsewhere (CPU tests) it runs
under the pallas interpreter.
"""
from .flash_attention import flash_attention  # noqa: F401
