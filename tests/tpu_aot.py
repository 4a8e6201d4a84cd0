"""Compile-only checks against a real TPU target: what the test_tpu_aot_*.py
files share.

libtpu ships the XLA:TPU and Mosaic compilers even where no chip is
attached: ``get_topology_desc("tpu", "v5e:2x2")`` hands out abstract v5e
devices, and lowering + compiling for one of them runs the same compilers
the chip run does. CPU tests otherwise only ever see ``interpret=True``,
so this is what keeps a Mosaic refusal (unsupported op, layout, VMEM
overflow) visible to tier-1. Nothing here executes.

The checks are split by what they compile (the training kernels and the
train step, the serving programs, the flash dispatcher's tiles in two
files) so that no one worker carries all their compiles. A process keeps
libtpu's lock until it exits: under several workers the files need
``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, as the driver's command sets it, or all
but the first worker's skip.
"""
import base64
import hashlib
import json
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas.flash_attention import flash_attention


@pytest.fixture(scope="module")
def tpu_topology():
    """Four abstract v5e devices (2x2): described, not attached."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / no compile-only support here
        pytest.skip(f"no compile-only TPU target: {type(e).__name__}: {e}")


@pytest.fixture(scope="module")
def tpu_device(tpu_topology):
    return tpu_topology.devices[0]


@pytest.fixture(scope="module")
def tpu_arg(tpu_device):
    """(shape, dtype) -> ShapeDtypeStruct placed on the abstract device."""
    sharding = SingleDeviceSharding(tpu_device)
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# stable names: every kernel and serving program is found by its NAME in the
# compiled HLO (and so in a device trace), at GPT-2 small's widths


def kernel_names(text):
    """Names of the Mosaic custom-call instructions, numeric suffix dropped."""
    names = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    return sorted(re.sub(r"\.\d+$", "", n) for n in names)


def own_names(names):
    """A kernel traced under jax.vjp or its transpose is jvp_<name>_ or
    transpose_jvp_<name>__: the kernel's own name is still in it."""
    own = re.compile(r"flash_(?:fwd|dq|dkv)|lmhead_ce_(?:stats|dx|dw)|fused_adam")
    return sorted(own.search(n).group(0) if own.search(n) else n for n in names)


def metric_pattern(metric):
    from benchmark import manifest

    return re.compile(manifest.layer_metric(metric)["args"]["pattern"])


def sha(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def mosaic_bodies(fn, *args):
    """The Mosaic module of every kernel `fn` lowers to for a TPU, as text
    (generic form, no locations): what Pallas hands the Mosaic compiler.
    Lowered and not compiled, so it needs no compile-only target."""
    from jax._src.lib.mlir import ir

    text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True  # the bytecode's versioned dialect, stable_mosaic
    out = []
    with ctx:
        for config in re.findall(r'backend_config = "(.*?[^\\])"', text, re.S):
            body = json.loads(config.replace("\\22", '"'))["custom_call_config"]["body"]
            out.append(ir.Module.parse(base64.b64decode(body)).operation.get_asm(enable_debug_info=False))
    return out


def the_dispatchers_tiles_compile_at_gpt2s_widths(tpu_arg, layout):
    """Mosaic takes the kernels at the tiles the dispatcher picks for a
    causal call of T 1024 and 12 heads of 64: the one-step forward with its
    trimmed parts, the clamped index maps and, since PR 43, for BTHD at the
    cell's own shape (B 32) ONE fused backward on kv tiles of 256 against
    the whole sequence's q rows: two Mosaic calls, and the backward's name
    is one the benchmark's pattern admits (flash_dkv); heads of 128 loop
    one a group; 16, 20 and 32 heads of 64 (24, 29 and 44 MiB of VMEM by the
    kernel's own count: the last is the widest its budget lets in, and the
    widest PR 43 ran on the chip) compile as well.
    BHTD keeps dq and dkv; so does the table's two-kernel entry (dq one
    step, dkv's tall tiles), which a call the fused kernel does not fit
    falls back to (25 heads of 64, GPT-2 XL's, on one device: an odd number
    of half-tile heads)."""
    from paddle_tpu.ops import attention

    layout, _, variant = layout.partition("_")
    heads, hd = {"1600_wide": (25, 64), "heads_of_128": (6, 128), "1024_wide": (16, 64),
                 "1280_wide": (20, 64), "2048_wide": (32, 64)}.get(variant, (12, 64))
    # (1 << 14 heads: no kernel is that wide, so the table's other entry)
    bq, bk, bwd = attention._flash_tiles(1024, 1024, layout, True, heads=1 << 14 if variant == "two_kernels" else heads,
                                         head_dim=hd)
    fused = layout == "BTHD" and variant not in ("two_kernels", "1600_wide")
    assert bwd == (("fused", 256) if fused else (128, 1024, 512, 256) if layout == "BTHD"
                   else (512, 1024, 512, 1024)), bwd

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk, bwd_blocks=bwd,
                               layout=layout, interpret=False).astype(jnp.float32).sum()

    batch = 32 if fused else 2
    qkv = [tpu_arg((batch, 1024, heads, hd) if layout == "BTHD" else (batch, heads, 1024, hd), jnp.bfloat16)] * 3
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]  # the package attribute is the function
    before = fa.fwd_body_counts()
    names = kernel_names(compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *qkv))
    # since PR 53 the one-step BTHD forward LOOPS over its head groups wherever the heads group into whole lane
    # tiles (every width here but GPT-2 XL's 25 heads of 64); BHTD walks its heads on the grid and is not counted
    took = {b: n - before[b] for b, n in fa.fwd_body_counts().items() if n != before[b]}
    assert took == ({} if layout == "BHTD" else {"unrolled": 1} if variant == "1600_wide" else {"looped": 1}), took
    assert own_names(names) == (["flash_dkv", "flash_fwd"] if fused else ["flash_dkv", "flash_dq", "flash_fwd"]), names
    rx = metric_pattern("flash_kernels_roofline")
    assert all(rx.search(n) for n in names), names
