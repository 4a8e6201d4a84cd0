"""Python half of the C inference API.

The reference C API (inference/capi/: PD_NewAnalysisConfig,
PD_NewPredictor, PD_SetZeroCopyInput, PD_ZeroCopyRun, ...) wraps the C++
AnalysisPredictor. Here the predictor is Python/XLA, so csrc/capi.cc
embeds the interpreter and calls these helpers; tensors cross the C
boundary as raw buffers + shape vectors (the zero-copy contract, one copy
at the language border).
"""
from __future__ import annotations

import os

import numpy as np

# the embedded interpreter has no conftest: honor an explicit platform pin
if os.environ.get("PADDLE_CAPI_PLATFORM"):
    import jax

    jax.config.update("jax_platforms", os.environ["PADDLE_CAPI_PLATFORM"])

_PREDICTORS = {}
_NEXT = [1]


def create(model_dir: str) -> int:
    from .predictor import Config, create_predictor

    pred = create_predictor(Config(model_dir))
    h = _NEXT[0]
    _NEXT[0] += 1
    _PREDICTORS[h] = pred
    return h


def destroy(h: int) -> None:
    _PREDICTORS.pop(h, None)


def input_names(h: int) -> list:
    return list(_PREDICTORS[h].get_input_names())


def output_names(h: int) -> list:
    return list(_PREDICTORS[h].get_output_names())


def run(h: int, in_blobs, in_shapes, in_dtypes):
    """in_blobs: list[bytes]; in_shapes: list[list[int]]; in_dtypes:
    list[str]. Returns (out_blobs, out_shapes, out_dtypes)."""
    pred = _PREDICTORS[h]
    ins = [
        np.frombuffer(b, dtype=np.dtype(dt)).reshape(shape)
        for b, shape, dt in zip(in_blobs, in_shapes, in_dtypes)
    ]
    outs = pred.run(ins)
    blobs, shapes, dtypes = [], [], []
    for o in outs:
        a = np.ascontiguousarray(np.asarray(o))
        blobs.append(a.tobytes())
        shapes.append(list(a.shape))
        dtypes.append(str(a.dtype))
    return blobs, shapes, dtypes
