"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle's
capabilities (reference snapshot ~v1.8/2.0-rc), built on JAX/XLA.

Programs (static graphs) and dygraph traces lower to XLA HLO and run as
single fused TPU executables; distribution rides `jax.sharding` meshes and
XLA collectives over ICI instead of NCCL rings. See SURVEY.md for the
architectural mapping to the reference.

Like paddle 2.0, dygraph is the default mode; call `enable_static()` for
graph building.
"""
__version__ = "0.1.0"

import time as _time

_IMPORT_T0 = _time.perf_counter()

from .framework import (
    CPUPlace,
    CUDAPlace,
    Executor,
    ParamAttr,
    Program,
    TPUPlace,
    append_backward,
    default_main_program,
    default_startup_program,
    get_device,
    global_scope,
    gradients,
    in_dygraph_mode,
    program_guard,
    set_device,
)
from . import static
from .framework import initializer

# fluid-compat namespace: `import paddle_tpu.fluid as fluid` style access
from . import fluid  # noqa: E402

# dygraph + eager tensor API
from .dygraph import Tensor, no_grad, to_tensor
from .dygraph.base import enable_dygraph, disable_dygraph

# functional tensor namespace (paddle.add / paddle.matmul / ...)
from .ops import api as _api
from .ops.api import (  # noqa: F401
    abs,
    add,
    arange,
    argmax,
    argmin,
    bmm,
    cast,
    clip,
    concat,
    cos,
    cumsum,
    divide,
    equal,
    exp,
    expand,
    flatten,
    full,
    gather,
    greater_equal,
    greater_than,
    less_equal,
    less_than,
    log,
    matmul,
    max,
    maximum,
    mean,
    min,
    minimum,
    multiply,
    not_equal,
    ones,
    ones_like,
    prod,
    reshape,
    rsqrt,
    scale,
    sigmoid,
    sin,
    softmax,
    split,
    sqrt,
    square,
    squeeze,
    stack,
    subtract,
    sum,
    tanh,
    tile,
    topk,
    transpose,
    tril,
    triu,
    unsqueeze,
    where,
    zeros,
    zeros_like,
)

_api._install_patches()

from . import nn  # noqa: E402
from . import optimizer  # noqa: E402
from . import metric  # noqa: E402
from . import io  # noqa: E402
from . import amp  # noqa: E402
from .flags import get_flags, set_flags  # noqa: E402
from . import regularizer  # noqa: E402
from .hapi.model_io import load, save  # noqa: E402
from .hapi.model import Model  # noqa: E402
from .distributed.parallel import DataParallel  # noqa: E402
from . import jit  # noqa: E402
from . import tensor  # noqa: E402
from . import callbacks  # noqa: E402
from . import device  # noqa: E402
from .framework.errors import (EnforceError, enforce, enforce_eq,  # noqa: E402,F401
                               enforce_ge, enforce_gt, enforce_le,
                               enforce_lt, enforce_ne, errors)
from . import inference  # noqa: E402
from . import dataset  # noqa: E402
from . import contrib  # noqa: E402
from . import monitor  # noqa: E402
from . import goodput  # noqa: E402
from . import memwatch  # noqa: E402  (PADDLE_TPU_MEMWATCH_DIR auto-journal)
from . import dynamics  # noqa: E402  (PADDLE_TPU_DYNAMICS_DIR auto-journal)
from . import status  # noqa: E402  (PADDLE_TPU_STATUS_PORT auto-serve)
from . import text  # noqa: E402
from .dataset import DatasetFactory, InMemoryDataset, QueueDataset  # noqa: E402,F401
from . import vision  # noqa: E402
from . import io  # noqa: E402
from . import metric  # noqa: E402
from . import optimizer  # noqa: E402


def enable_static():
    disable_dygraph()


def disable_static():
    enable_dygraph()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def seed(value: int):
    """Set the global random seed (reference paddle.seed)."""
    from .framework import program as _fw

    tracer = _fw._current_tracer()
    if tracer is not None:
        # stays lazy: the key materializes on the first traced op, so
        # seeding never initializes the device backend by itself
        tracer._seed = value
        tracer._base_key = None
    default_main_program().random_seed = value
    return value


# dygraph by default (paddle 2.0 semantics)
enable_dygraph()


def summary(net, input_size=None, dtypes="float32"):
    """Reference paddle.summary: per-layer table for a bare nn.Layer.
    A -1/None batch dim becomes 1 (the reference substitutes the same);
    `dtypes` accepts a string or a list (the first entry applies to all
    inputs — per-input dtypes are not differentiated yet)."""

    def _clean(sz):
        return [1 if (d is None or d == -1) else int(d) for d in sz]

    sizes = input_size
    if sizes is not None:
        if isinstance(sizes, (list, tuple)) and sizes \
                and isinstance(sizes[0], (list, tuple)):
            sizes = [_clean(sz) for sz in sizes]
        else:
            sizes = _clean(sizes)
    dt = dtypes[0] if isinstance(dtypes, (list, tuple)) else dtypes
    return Model(net).summary(input_size=sizes, dtype=dt)


# what `import paddle_tpu` cost this process (jax and its backends' Python
# included where nothing imported them before): a part of every set-up
# that no jit event sees (framework/xla_insight.build_log has those)
monitor.gauge(
    "paddle_tpu_import_seconds",
    "wall seconds `import paddle_tpu` took in this process").set(
        _time.perf_counter() - _IMPORT_T0)
