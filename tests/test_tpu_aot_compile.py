"""Compile-only check of the pallas kernels against a real TPU target.

libtpu ships the XLA:TPU and Mosaic compilers even where no chip is
attached: ``get_topology_desc("tpu", "v5e:2x2")`` hands out abstract v5e
devices, and lowering + compiling for one of them runs the same compilers
the chip run does. CPU tests otherwise only ever see ``interpret=True``,
so this is what keeps a Mosaic refusal (unsupported op, layout, VMEM
overflow) visible to tier-1. Nothing here executes.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.fused_adam import fused_adam
from paddle_tpu.ops.pallas.fused_lmhead_ce import lmhead_ce


@pytest.fixture(scope="module")
def tpu_arg():
    """(shape, dtype) -> ShapeDtypeStruct placed on an abstract v5e device."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / no compile-only support here
        pytest.skip(f"no compile-only TPU target: {type(e).__name__}: {e}")
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_fwd_bwd_compiles(tpu_arg):
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, block_q=256, block_k=256,
                              layout="BTHD", interpret=False)
        return out.astype(jnp.float32).sum()

    qkv = [tpu_arg((1, 512, 4, 64), jnp.bfloat16)] * 3
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *qkv)
    assert text.count("tpu_custom_call") >= 3  # fwd, dq, dkv


def test_lmhead_ce_fwd_bwd_compiles(tpu_arg):
    def loss(x, w, labels):
        return lmhead_ce(x, w, labels, interpret=False).sum()

    text = _compiled_text(
        jax.grad(loss, argnums=(0, 1)),
        tpu_arg((512, 256), jnp.bfloat16), tpu_arg((2048, 256), jnp.bfloat16),
        tpu_arg((512,), jnp.int32))
    assert text.count("tpu_custom_call") >= 3  # stats, dx, dw


def test_fused_adam_compiles(tpu_arg):
    p = tpu_arg((512, 256), jnp.bfloat16)
    m = tpu_arg((512, 256), jnp.float32)
    s = tpu_arg((), jnp.float32)
    text = _compiled_text(functools.partial(fused_adam, interpret=False),
                          p, p, m, m, s, s, s)
    assert "tpu_custom_call" in text


def test_oversize_tile_is_refused(tpu_arg):
    """The check is live: a (2048, 8192) f32 score tile cannot fit VMEM,
    and the compile-only target says so like the chip would."""
    def loss(x, w, labels):
        return lmhead_ce(x, w, labels, block_n=2048, block_v=8192,
                         interpret=False).sum()

    with pytest.raises(Exception, match="vmem"):
        _compiled_text(
            loss, tpu_arg((2048, 128), jnp.bfloat16),
            tpu_arg((8192, 128), jnp.bfloat16), tpu_arg((2048,), jnp.int32))
