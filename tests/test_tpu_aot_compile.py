"""Compile-only check of the pallas kernels against a real TPU target.

libtpu ships the XLA:TPU and Mosaic compilers even where no chip is
attached: ``get_topology_desc("tpu", "v5e:2x2")`` hands out abstract v5e
devices, and lowering + compiling for one of them runs the same compilers
the chip run does. CPU tests otherwise only ever see ``interpret=True``,
so this is what keeps a Mosaic refusal (unsupported op, layout, VMEM
overflow) visible to tier-1. Nothing here executes.
"""
import functools
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.fused_adam import fused_adam
from paddle_tpu.ops.pallas.fused_lmhead_ce import lmhead_ce


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
    assert text.count("tpu_custom_call") >= 2  # stats; dx and dw from one kernel


def test_fused_adam_compiles(tpu_arg):
    p = tpu_arg((512, 256), jnp.bfloat16)
    m = tpu_arg((512, 256), jnp.float32)
    s = tpu_arg((), jnp.float32)
    text = _compiled_text(functools.partial(fused_adam, interpret=False),
                          p, p, m, m, s, s, s)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B,H,hd,rows", [(12, 25, 64, 48 * 432), (24, 16, 128, 12 * 960)])
def test_paged_attention_compiles_at_both_serving_cells_shapes(tpu_arg, B, H, hd, rows):
    """The decode kernel alone, over a whole cell's pool (described, not
    allocated): a head of one 128-lane tile (GPT-2 XL) and of two (OLMoE)."""
    from paddle_tpu.ops.pallas.paged_attention import paged_attention

    text = _compiled_text(
        functools.partial(paged_attention, scale=1.0 / math.sqrt(hd), interpret=False),
        tpu_arg((B, H, hd), jnp.bfloat16), tpu_arg((rows, 16, H * 2 * hd), jnp.bfloat16),
        tpu_arg((B, 64), jnp.int32), tpu_arg((B,), jnp.int32))
    assert _kernel_names(text) == ["paged_attention"]
    # the pool goes to the kernel as it rests: no copy or relayout of it
    assert not re.search(rf"bf16\[{rows},16,{H * 2 * hd}\][^\n]* (copy|transpose|reshape)\(", text)


def test_oversize_tile_is_refused(tpu_arg):
    """The check is live: at D 16384 the double-buffered (1024, D) x block
    alone is the 64 MB a kernel may use, and the compile-only target
    says so like the chip would. (A large f32 score tile alone does not
    overflow: Mosaic computes it in pieces.)"""
    def loss(x, w, labels):
        return lmhead_ce(x, w, labels, block_n=1024, block_v=256,
                         interpret=False).sum()

    with pytest.raises(Exception, match="vmem"):
        _compiled_text(
            loss, tpu_arg((1024, 16384), jnp.bfloat16),
            tpu_arg((256, 16384), jnp.bfloat16), tpu_arg((1024,), jnp.int32))


# ---------------------------------------------------------------------------
# stable names: every kernel and serving program is found by its NAME in the
# compiled HLO (and so in a device trace), at GPT-2 small's widths
# ---------------------------------------------------------------------------

import re  # noqa: E402


def _kernel_names(text):
    """Names of the Mosaic custom-call instructions, numeric suffix dropped."""
    names = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    return sorted(re.sub(r"\.\d+$", "", n) for n in names)


def _own_names(names):
    """A kernel traced under jax.vjp or its transpose is jvp_<name>_ or
    transpose_jvp_<name>__: the kernel's own name is still in it."""
    own = re.compile(r"flash_(?:fwd|dq|dkv)|lmhead_ce_(?:stats|dx|dw)|fused_adam")
    return sorted(own.search(n).group(0) if own.search(n) else n for n in names)


def _metric_pattern(metric):
    from benchmark import manifest

    return re.compile(manifest.layer_metric(metric)["args"]["pattern"])


def test_flash_kernels_carry_their_names_at_gpt2s_widths(tpu_arg):
    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, layout="BTHD", interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    qkv = [tpu_arg((2, 1024, 12, 64), jnp.bfloat16)] * 3
    assert _kernel_names(_compiled_text(fwd, *qkv)) == ["flash_fwd"]
    names = _kernel_names(_compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *qkv))
    assert _own_names(names) == ["flash_dkv", "flash_dq", "flash_fwd"], names
    rx, fwd_rx = _metric_pattern("flash_kernels_roofline"), _metric_pattern("fwd_passes_per_step")
    assert all(rx.search(n) for n in names)
    assert sum(bool(fwd_rx.search(n)) for n in names) == 1


@pytest.mark.parametrize("layout", ["BTHD", "BHTD", "BTHD_two_kernels", "BTHD_1600_wide", "BTHD_heads_of_128",
                                    "BTHD_1024_wide", "BTHD_1280_wide", "BTHD_2048_wide"])
def test_the_dispatchers_tiles_compile_at_gpt2s_widths(tpu_arg, monkeypatch, layout):
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

    for knob in ("PADDLE_TPU_FLASH_BLOCKS", "PADDLE_TPU_FLASH_BWD_BLOCKS"):
        monkeypatch.delenv(knob, raising=False)
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
    names = _kernel_names(_compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *qkv))
    assert _own_names(names) == (["flash_dkv", "flash_fwd"] if fused else ["flash_dkv", "flash_dq", "flash_fwd"]), names
    rx = _metric_pattern("flash_kernels_roofline")
    assert all(rx.search(n) for n in names), names


def test_lmhead_ce_kernels_carry_their_names_at_gpt2s_widths(tpu_arg):
    """Two kernels since PR 40: the forward sweep, and ONE backward that
    gives dx and dW from one rematerialised tile. It is named
    lmhead_ce_dw because the benchmark's pattern admits stats|dx|dw and
    no PR that claims a gain may edit it."""
    def loss(x, w, labels):
        return lmhead_ce(x, w, labels, interpret=False).sum()

    args = (tpu_arg((2048, 768), jnp.bfloat16), tpu_arg((50304, 768), jnp.bfloat16),
            tpu_arg((2048,), jnp.int32))
    assert _kernel_names(_compiled_text(loss, *args)) == ["lmhead_ce_stats"]
    names = _kernel_names(_compiled_text(jax.grad(loss, argnums=(0, 1)), *args))
    assert _own_names(names) == ["lmhead_ce_dw", "lmhead_ce_stats"], names
    rx = _metric_pattern("lmhead_ce_kernels_roofline")
    assert all(rx.search(n) for n in names)
    assert not rx.search("flash_fwd") and not _metric_pattern("flash_kernels_roofline").search(names[0])


@pytest.mark.parametrize("n,d,v,want,vp", [(32768, 768, 50304, (1024, 768), 50688),
                                           (1024, 1600, 50304, (1024, 256), 50432)])
def test_lmhead_ce_compiles_on_the_dispatchers_tiles_at_both_training_cells_shapes(tpu_arg, n, d, v, want, vp):
    """Forward + backward at gpt2s-train-1k's call and at the call one
    chip of gpt2xl-train-fsdp4 makes (1,024 tokens, D 1600: no lane
    multiple), on the tiles the dispatcher picks from the shape: the VMEM
    budget of those tiles holds under Mosaic, no chip needed. At the
    first the weight reaches both kernels padded to 50,688 rows, x before
    w, which is how the benchmark's shape-based lmhead_ce_roofline finds
    them in gpt2s-train-1k."""
    from paddle_tpu.ops.pallas.fused_lmhead_ce import tiles

    def loss(x, w, labels):
        return lmhead_ce(x, w, labels, interpret=False).sum()

    assert tiles(n, d, v) == want
    text = _compiled_text(jax.grad(loss, argnums=(0, 1)), tpu_arg((n, d), jnp.bfloat16),
                          tpu_arg((v, d), jnp.bfloat16), tpu_arg((n,), jnp.int32))
    calls = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert _own_names(_kernel_names(text)) == ["lmhead_ce_dw", "lmhead_ce_stats"]
    for ln in calls:
        operands = ln.split("operand_layout_constraints=")[1]
        assert operands.index(f"bf16[{n},{d}]") < operands.index(f"bf16[{vp},{d}]"), ln
    # no [tokens, vocab] array anywhere in the program: the logits tile stays in VMEM
    assert not re.search(rf"\[{n},50\d\d\d\]|\[50\d\d\d,{n}\]", text)


def test_sharded_lmhead_ce_compiles_for_fsdp4s_four_chips(tpu_topology):
    """gpt2xl-train-fsdp4's loss: lmhead_ce_sharded over four described
    chips, rows and the weight's vocab dim sharded on fsdp, the weight
    gathered at use. A chip's call is (1024, 1600, 50304): one token
    block, so dW leaves the kernel cast (as the parent's did) and no f32
    accumulator is kept in HBM; both kernels sit in the shard_map region
    under their names."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.pallas.fused_lmhead_ce import lmhead_ce_sharded

    mesh = Mesh(np.array(tpu_topology.devices).reshape(4), ("fsdp",))
    arg = lambda shape, dtype, spec: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=NamedSharding(mesh, spec))

    def loss(x, w, labels):
        return lmhead_ce_sharded(x, w, labels, mesh, batch_axes=("fsdp",), gather_axis="fsdp",
                                 interpret=False).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1)), arg((4096, 1600), jnp.bfloat16, P("fsdp", None)),
                          arg((50304, 1600), jnp.bfloat16, P("fsdp", None)), arg((4096,), jnp.int32, P("fsdp")))
    assert _kernel_names(text) == ["lmhead_ce_dw", "lmhead_ce_stats"]
    assert "all-gather" in text
    (dw_call,) = [ln for ln in text.splitlines() if "%lmhead_ce_dw" in ln and "tpu_custom_call" in ln]
    assert "bf16[50432,1600]" in dw_call.split(" custom-call(")[0] and "f32[50" not in dw_call


def test_fused_adam_kernel_carries_its_name_at_gpt2s_widths(tpu_arg):
    p = tpu_arg((768, 3072), jnp.bfloat16)
    m = tpu_arg((768, 3072), jnp.float32)
    s = tpu_arg((), jnp.float32)
    text = _compiled_text(functools.partial(fused_adam, interpret=False), p, p, m, m, s, s, s)
    assert _kernel_names(text) == ["fused_adam"]


def _xl_serving_programs(tpu_device, vocab_size):
    """The decode and one prefill program of a DecodeModel at the serving
    cells' widths (GPT-2 XL's heads, slots and blocks; 2 layers, no weight
    allocated), compiled for the described chip through the model's own
    jit wrapper and pool description. ``.text`` and ``.facts`` by program
    name (tools/serve_compile_report.py reads the facts)."""
    import os
    import sys
    import types

    from paddle_tpu import serving

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import serve_compile_report as report

    cfg = serving.GPTConfig(vocab_size=vocab_size, n_layer=2, n_head=25, d_model=1600, max_seq_len=1024,
                            dtype="bfloat16")
    dm = report.abstract_model(cfg, max_batch=12, n_blocks=432, block_size=16, prefill_buckets=[256])
    out = types.SimpleNamespace(dm=dm, text={}, facts={})
    for name, (jit_fn, args) in report.serving_programs(dm).items():
        compiled = report.compile_on(jit_fn, args, tpu_device)
        out.text[name] = compiled.as_text()
        out.facts[name] = report.describe(compiled, dm.pool_shape())
    return out


@pytest.fixture(scope="module")
def serving_programs(tpu_device):
    """At a small vocabulary (1,024): what the pool's tests need."""
    return _xl_serving_programs(tpu_device, 1024)


@pytest.fixture(scope="module")
def xl_table_programs(tpu_device):
    """At GPT-2 XL's TRUE table, vocabulary 50,304: a copy of it shows."""
    return _xl_serving_programs(tpu_device, 50304)


_SERVING_PROGRAMS = ["decode_tick", "prefill_256"]


@pytest.mark.parametrize("name", _SERVING_PROGRAMS)
def test_serving_programs_carry_their_names(serving_programs, name):
    text = serving_programs.text[name]
    assert re.search(rf"HloModule jit_{name}\b", text)
    from benchmark import manifest

    metric = "decode_program_ms" if name == "decode_tick" else "prefill_program_ms"
    assert re.search(manifest.layer_metric(metric)["args"]["pattern"], f"jit_{name}")


@pytest.mark.parametrize("name,scopes", [
    ("decode_tick", ("embed", "jit(layer)/attn/kv_write", "jit(layer)/attn/paged", "jit(layer)/mlp",
                     "lm_head")),
    ("prefill_256", ("embed", "jit(layer)/attn/kv_write", "jit(layer)/attn/scores", "jit(layer)/mlp",
                     "lm_head"))])
def test_serving_programs_carry_their_scopes_in_op_name(serving_programs, name, scopes):
    """The layer body is an inner jit named ``layer`` (traced once for all
    layers), so its scopes read ``jit(<program>)/jit(layer)/attn/...``. On
    one device the decode tick attends inside ``attn/paged`` (the kernel);
    no window is gathered, so no ``attn/kv_gather`` is left."""
    ops = set(re.findall(r'op_name="([^"]*)"', serving_programs.text[name]))
    for scope in scopes:
        assert any(f"jit({name})/{scope}/" in o for o in ops), (scope, sorted(ops)[:20])
    assert not any("/attn/kv_gather/" in o for o in ops)


def test_compile_report_says_how_the_decode_tick_attends(serving_programs):
    """tools/serve_compile_report.py: the kernel's calls and VMEM scratch
    per program, and for a model the kernel cannot take, why, unrun."""
    import serve_compile_report as report

    from paddle_tpu import serving

    dm, facts = serving_programs.dm, serving_programs.facts
    assert facts["decode_tick"]["mosaic_kernels"] == {"paged_attention": dm.cfg.n_layer}
    assert facts["prefill_256"]["mosaic_kernels"] == {}
    att = report.attention_facts(dm, facts["decode_tick"]["mosaic_kernels"])
    assert att["decode_path"] == "kernel" and att["paged_attention_calls"] == 2
    # two buffers of 128 rows of 3,200 bf16 lanes, the float32 accumulator, two statistics
    assert att["vmem_scratch_bytes"] == 2 * 128 * 3200 * 2 + 32 * 3200 * 4 + 2 * 32 * 128 * 4
    assert att["vmem_scratch_bytes"] < 16 * 2 ** 20
    narrow = report.abstract_model(
        serving.GPTConfig(vocab_size=64, n_layer=1, n_head=50, d_model=1600, max_seq_len=64,
                          dtype="bfloat16"), max_batch=2, n_blocks=8, block_size=16, prefill_buckets=[16])
    att = report.attention_facts(narrow, {})
    assert att["decode_path"] == "gather" and "64 lanes" in att["why"] and att["vmem_scratch_bytes"] == 0


def test_decode_tick_holds_one_paged_attention_kernel_a_layer(serving_programs):
    """25 heads of 64: a head's K|V is ONE 128-lane tile of the row."""
    dm = serving_programs.dm
    assert dm.attention_path() == ("kernel", "")
    assert _kernel_names(serving_programs.text["decode_tick"]) == ["paged_attention"] * dm.cfg.n_layer
    assert _kernel_names(serving_programs.text["prefill_256"]) == []


# The KV pool stays where it is (PERF.md, PR 25): XLA:TPU stores an array in
# the most compact tiled layout for its SHAPE, and a program that gathers
# and scatters in another layout copies the whole pool in and out.


@pytest.mark.parametrize("name", _SERVING_PROGRAMS)
def test_serving_program_updates_the_pool_in_place(serving_programs, name):
    pool = serving_programs.facts[name]["pool"]
    assert pool["parameter"] is not None and pool["aliased_to_output"], pool


@pytest.mark.parametrize("name", _SERVING_PROGRAMS)
def test_pool_rests_in_the_layout_its_scatter_works_on(serving_programs, name):
    pool = serving_programs.facts[name]["pool"]
    assert pool["layouts_in_program"] == [pool["layout"]], pool
    assert pool["layout"].startswith("2,1,0:T(8,128)"), pool  # row-major, 128-lane rows


@pytest.mark.parametrize("name", _SERVING_PROGRAMS)
def test_serving_program_copies_neither_pool_nor_gathered_context(serving_programs, name):
    dm = serving_programs.dm
    limit = min(math.prod(dm.pool_shape()), dm.max_batch * dm.gather_len * dm.cfg.d_model)
    big = [c for c in serving_programs.facts[name]["top_level_copies"] if c["elements"] >= limit]
    assert not big, big


# The embedding table stays where it is too (PERF.md, PR 47): `[V, D]` with
# D no multiple of 128 rests with the VOCABULARY on the lanes, the most
# compact tiling of its shape. The tied head's matmul reads it as it lies;
# a row gather first copies all of it to row-major, every tick. The fixture
# above, at vocabulary 1,024, is too small to show it.


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("shape", [(50304, 1600), (50257, 1600), (1024, 1600), (50304, 1608), (128, 32), (4096, 64),
                                   (50304, 1536), (50304, 2048), (65536, 2048), (512, 256), (200, 100)])
def test_a_table_rests_in_the_most_compact_tiling_of_its_shape(tpu_arg, shape, dtype):
    """``serving.model.rests_lanes_first`` is the compiler's own rule: the
    entry layout of a parameter follows from its shape alone, whatever
    reads it."""
    from paddle_tpu.serving.model import rests_lanes_first

    text = _compiled_text(lambda w: w + 1, tpu_arg(shape, jnp.dtype(dtype)))
    layout = re.search(r"entry_computation_layout=\{\(\w+\[[\d,]+\]\{([\d,]+):", text).group(1)
    assert layout == ("0,1" if rests_lanes_first(shape) else "1,0"), (shape, layout)


def _embed_facts(progs, name):
    import serve_compile_report as report

    return report.embed_facts(progs.dm, progs.text[name], progs.facts[name]["top_level_copies"])


def test_decode_tick_looks_up_its_rows_in_the_table_as_it_lies(xl_table_programs):
    """A slice a slot: no copy as large as the table, and the table named
    in ONE layout throughout the program, the one it rests in."""
    assert xl_table_programs.dm.embed_path()[0] == "slices"
    emb = _embed_facts(xl_table_programs, "decode_tick")
    assert emb["table_sized_copies"] == 0 and emb["table_layouts"] == ["0,1:T(8,128)(2,1)"], emb
    # and the temporaries no longer hold a second table (161 MB)
    assert xl_table_programs.facts["decode_tick"]["memory"]["temp_size_in_bytes"] < 16 * 2 ** 20


def test_prefill_still_copies_the_table_once(xl_table_programs):
    """Prefill keeps the gather on purpose (256 row slices read a third of
    what the copy moves, 1,024 more than it): the day someone cures it,
    this says so."""
    pre = _embed_facts(xl_table_programs, "prefill_256")
    assert pre["table_sized_copies"] == 1 and {lay[:3] for lay in pre["table_layouts"]} == {"0,1", "1,0"}, pre
    copies = xl_table_programs.facts["prefill_256"]["top_level_copies"]
    assert [c["count"] for c in copies if (c["op"], c["shape"]) == ("copy", "bf16[50304,1600]")] == [1], copies


def test_compile_report_says_how_the_decode_tick_looks_up_its_rows(xl_table_programs):
    """tools/serve_compile_report.py: the path and its reason beside the
    table's layouts and the table-sized copies, per program."""
    emb = _embed_facts(xl_table_programs, "decode_tick")
    assert sorted(emb) == ["decode_path", "table", "table_layouts", "table_sized_copies", "why"]
    assert emb["decode_path"] == "slices" and "vocabulary-on-lanes" in emb["why"] and "1600" in emb["why"]
    assert emb["table"] == [50304, 1600]
    assert _embed_facts(xl_table_programs, "prefill_256")["decode_path"] == "slices"  # the model's, not the program's


@pytest.mark.parametrize("programs,table,slots,tied", [("lfm2_programs", (65536, 2048), 64, True),
                                                       ("olmoe_programs", (50304, 2048), 24, False)])
@pytest.mark.parametrize("name", _SERVING_PROGRAMS)
def test_a_2048_wide_table_rests_row_major_and_keeps_the_gather(request, programs, table, slots, tied, name):
    """LFM2's tied ``[65536, 2048]`` and OLMoE's untied ``[50304, 2048]``:
    whole lane tiles a row, so the table rests row-major, one gather takes
    the rows and neither program copies anything as large as the table."""
    progs = request.getfixturevalue(programs)
    dm = progs.dm
    assert (dm.cfg.vocab_size, dm.cfg.d_model) == table and dm.max_batch == slots
    assert dm.cfg.tie_embeddings == tied
    path, why = dm.embed_path()
    assert path == "gather" and "row-major" in why
    emb = _embed_facts(progs, name)
    assert emb["decode_path"] == "gather" and emb["table_sized_copies"] == 0, emb
    assert {lay[:3] for lay in emb["table_layouts"]} == {"1,0"}, emb


@pytest.mark.parametrize("name", _SERVING_PROGRAMS)
def test_serving_program_holds_no_second_pool(serving_programs, name):
    """Temporaries stay under the pool plus one layer's activations: no
    gathered context is among them any more (78.6 MB a layer before the
    kernel read the pages where they lie)."""
    dm = serving_programs.dm
    cfg = dm.cfg
    pool_bytes = 2 * math.prod(dm.pool_shape())
    rows = dm.max_batch if name == "decode_tick" else 256
    activations = rows * (cfg.ffn_dim + 8 * cfg.d_model + cfg.vocab_size) * 4
    temp = serving_programs.facts[name]["memory"]["temp_size_in_bytes"]
    assert temp < pool_bytes + activations, (temp, pool_bytes, activations)
    if name == "decode_tick":
        context_bytes = 2 * dm.max_batch * dm.gather_len * dm.pool_shape()[-1]
        assert temp < context_bytes / 4, (temp, context_bytes)


@pytest.mark.parametrize("name", _SERVING_PROGRAMS)
def test_pool_at_rest_is_not_padded(serving_programs, name):
    """The pool is the only aliased argument, so the aliased bytes are the
    pool as stored: L x NB x BS tokens of K and V, d_model wide, bf16."""
    dm, facts = serving_programs.dm, serving_programs.facts[name]
    assert facts["aliased_parameters"] == [facts["pool"]["parameter"]]
    need = dm.cfg.n_layer * dm.n_blocks * dm.block_size * 2 * dm.cfg.d_model * 2
    assert abs(facts["memory"]["alias_size_in_bytes"] - need) <= 0.01 * need


def test_executor_step_carries_paddle_ops_in_op_name_and_a_role_name(tpu_arg):
    """A tiny train step lowered for the chip: module jit_train_step, every
    instruction under its Paddle op's scope (XLA attention at this size)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.framework import Executor, Scope, program_guard
    from paddle_tpu.models.gpt import GPTConfig, build_train_program
    from paddle_tpu.optimizer import SGD

    paddle.enable_static()
    try:
        cfg = GPTConfig(vocab_size=64, n_layer=1, n_head=2, d_model=32, max_seq_len=16)
        main, startup, io = build_train_program(cfg, batch=2, seq=16)
        with program_guard(main, startup):
            SGD(learning_rate=0.1).minimize(io["loss"])
        scope, exe = Scope(), Executor()
        exe.run(startup, scope=scope)
        feeds = {"tokens": jnp.zeros((2, 16), jnp.int32), "labels": jnp.zeros((2, 16), jnp.int32)}
        for n, fn in (getattr(main, "_extra_feeds", None) or {}).items():
            feeds[n] = jnp.asarray(fn())
        compiled = exe._get_compiled(main, feeds, [io["loss"].name], scope)
        startup_names = [c.module_name for c in exe._cache.values()]
    finally:
        paddle.disable_static()
    assert startup_names == ["jit_startup", "jit_train_step"]
    spec = lambda a: tpu_arg(np.shape(a), a.dtype)  # noqa: E731
    text = compiled.fn.lower(
        {k: spec(v) for k, v in feeds.items()},
        {n: spec(scope.get(n)) for n in compiled.mutable_names},
        {n: spec(scope.get(n)) for n in compiled.const_names},
        tpu_arg((2,), jnp.uint32)).compile().as_text()
    assert re.search(r"HloModule jit_train_step\b", text)
    ops = set(re.findall(r'op_name="([^"]*)"', text))
    for paddle_op in ("layer_norm", "matmul_grad", "sgd"):
        assert any(f"jit(train_step)/{paddle_op}/" in o for o in ops), paddle_op


def test_train_step_runs_every_pallas_forward_once_and_backward_under_its_grad_op(tpu_arg, monkeypatch):
    """The chip's train step at GPT-2 small's widths, seq 1024 (so flash
    dispatches) and the pallas CE, 2 layers: a forward op is differentiated
    where it is traced, so each forward kernel is in the program once (XLA
    does not merge Mosaic calls: a second trace of the rule would be a
    second kernel), and the backward kernels sit under their grad op's scope."""
    import sys

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import monitor
    from paddle_tpu.framework import Executor, Scope, program_guard
    from paddle_tpu.models.gpt import GPTConfig, build_train_program
    from paddle_tpu.optimizer import SGD

    for mod in ("flash_attention", "fused_lmhead_ce", "backend"):
        monkeypatch.setattr(sys.modules[f"paddle_tpu.ops.pallas.{mod}"], "on_tpu", lambda: True)
    n_layer, B, T = 2, 2, 1024
    counters = {n: monitor.default_registry().get(n) for n in
                ("executor_grad_paired_total", "executor_grad_retraced_total")}
    paddle.enable_static()
    try:
        cfg = GPTConfig(vocab_size=50304, n_layer=n_layer, n_head=12, d_model=768, max_seq_len=T,
                        dropout=0.0, dtype="bfloat16", fused_lm_head="pallas")
        main, startup, io = build_train_program(cfg, batch=B, seq=T)
        with program_guard(main, startup):
            SGD(learning_rate=0.1).minimize(io["loss"])
        assert io["lm_head_impl"] == "pallas"
        scope, exe = Scope(), Executor()
        exe.run(startup, scope=scope)
        feeds = {"tokens": jnp.zeros((B, T), jnp.int32), "labels": jnp.zeros((B, T), jnp.int32)}
        for n, fn in (getattr(main, "_extra_feeds", None) or {}).items():
            feeds[n] = jnp.asarray(fn())
        compiled = exe._get_compiled(main, feeds, [io["loss"].name], scope)
    finally:
        paddle.disable_static()
    before = {n: c.value for n, c in counters.items()}
    spec = lambda a: tpu_arg(np.shape(a), a.dtype)  # noqa: E731
    text = compiled.fn.lower(
        {k: spec(v) for k, v in feeds.items()},
        {n: spec(scope.get(n)) for n in compiled.mutable_names},
        {n: spec(scope.get(n)) for n in compiled.const_names},
        tpu_arg((2,), jnp.uint32)).compile().as_text()
    generic_grads = sum(op.type.endswith("_grad") for op in main.global_block().ops)
    assert counters["executor_grad_paired_total"].value - before["executor_grad_paired_total"] == generic_grads
    assert counters["executor_grad_retraced_total"].value == before["executor_grad_retraced_total"]

    calls = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"[^\n]*op_name=\"([^\"]*)\"", text)
    assert len(calls) == text.count('custom_call_target="tpu_custom_call"')
    own = _own_names([re.sub(r"\.\d+$", "", n) for n, _ in calls])
    # since PR 43 the flash backward is ONE kernel (dq, dk, dv), named flash_dkv
    assert {k: own.count(k) for k in set(own)} == {
        "flash_fwd": n_layer, "flash_dkv": n_layer,
        "lmhead_ce_stats": 1, "lmhead_ce_dw": 1}
    fwd_rx = _metric_pattern("fwd_passes_per_step")
    assert sum(bool(fwd_rx.search(re.sub(r"\.\d+$", "", n))) for n, _ in calls) == n_layer
    scope_of = {"flash_fwd": "fused_attention_tpu/",
                "flash_dkv": "fused_attention_tpu_grad/", "lmhead_ce_stats": "fused_lm_head_ce/",
                "lmhead_ce_dw": "fused_lm_head_ce_grad/"}
    for name, op_name in calls:
        (kernel,) = _own_names([name])
        assert op_name.startswith("jit(train_step)/" + scope_of[kernel]), (name, op_name)


def _pallas_eqns(jaxpr):
    """Every pallas_call equation of a jaxpr, sub-jaxprs (jit, custom_vjp,
    cond, scan bodies) included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _pallas_eqns(sub)
    return out


def _pallas_calls(jaxpr):
    """[(kernel name, grid)] of every pallas_call in a jaxpr."""
    return [(str(getattr(eqn.params.get("name_and_src_info"), "name", None) or eqn.params.get("name")),
             tuple(eqn.params["grid_mapping"].grid)) for eqn in _pallas_eqns(jaxpr)]


def test_the_cells_train_step_holds_100_kernels_on_the_tables_grids(monkeypatch):
    """gpt2s-train-1k's own program (12 layers, batch 32, seq 1024, Adam),
    traced as the chip traces it and not compiled: 100 Mosaic calls (112
    until PR 43) = 74 fused Adam + 2 of the CE (the forward sweep and,
    since PR 40, ONE backward, both on 32 token blocks x 66 vocab tiles:
    the weight padded to 50,688 rows) + 12 each of the TWO flash kernels,
    on the grids the dispatcher's table gives at T 1024: the forward
    256 x 1024 in ONE kv step and, since PR 43, ONE backward (dq, dk and
    dv; named flash_dkv) on four kv tiles of 256 against the sequence's q
    rows; and what flash_tiles_total counts for the 12 layers' calls: no
    kernel computes more than 10 of 16 parts of the score square, and dq
    counts nothing."""
    import sys
    from collections import Counter

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.framework import Executor, Scope, program_guard
    from paddle_tpu.models.gpt import GPTConfig, build_train_program
    from paddle_tpu.optimizer import Adam

    for mod in ("flash_attention", "fused_lmhead_ce", "backend"):
        monkeypatch.setattr(sys.modules[f"paddle_tpu.ops.pallas.{mod}"], "on_tpu", lambda: True)
    for knob in ("PADDLE_TPU_FLASH_BLOCKS", "PADDLE_TPU_FLASH_BWD_BLOCKS", "PADDLE_TPU_FLASH_MIN_SEQ"):
        monkeypatch.delenv(knob, raising=False)
    n_layer, B, T = 12, 32, 1024
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    read = lambda: {(k, c): n for k, by_cls in fa.tile_counts().items()  # noqa: E731
                    for c, n in by_cls.items()}
    paddle.enable_static()
    try:
        cfg = GPTConfig(vocab_size=50304, n_layer=n_layer, n_head=12, d_model=768, max_seq_len=T,
                        dropout=0.0, dtype="bfloat16")
        main, startup, io = build_train_program(cfg, batch=B, seq=T)
        with program_guard(main, startup):
            Adam(learning_rate=1e-4).minimize(io["loss"])
        scope, exe = Scope(), Executor()
        exe.run(startup, scope=scope)
        feeds = {"tokens": jnp.zeros((B, T), jnp.int32), "labels": jnp.zeros((B, T), jnp.int32)}
        for n, fn in (getattr(main, "_extra_feeds", None) or {}).items():
            feeds[n] = jnp.asarray(fn())
        compiled = exe._get_compiled(main, feeds, [io["loss"].name], scope)
    finally:
        paddle.disable_static()
    spec = lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype)  # noqa: E731
    before = read()
    jaxpr = jax.make_jaxpr(compiled.fn)(
        {k: spec(v) for k, v in feeds.items()},
        {n: spec(scope.get(n)) for n in compiled.mutable_names},
        {n: spec(scope.get(n)) for n in compiled.const_names},
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    counted = {key: n - before[key] for key, n in read().items()}
    calls = _pallas_calls(jaxpr.jaxpr)
    names = Counter(name for name, _ in calls)
    assert len(calls) == 100 and names == {
        "fused_adam": 74, "lmhead_ce_stats": 1, "lmhead_ce_dw": 1,
        "flash_fwd": n_layer, "flash_dkv": n_layer}, names
    grids = {name: {g for n, g in calls if n == name} for name in names if name != "fused_adam"}
    assert grids == {"flash_fwd": {(B, 4, 1)}, "flash_dkv": {(B, 4)},
                     "lmhead_ce_stats": {(32, 66)}, "lmhead_ce_dw": {(32, 66)}}
    per_plane = {"fwd": (6, 0, 10), "dq": (0, 0, 0), "dkv": (6, 0, 10)}  # squares of 256
    for kernel, want in per_plane.items():
        got = [counted[kernel, c] for c in ("skipped", "interior", "diagonal")]
        assert got == [n_layer * B * n for n in want], (kernel, counted)
        assert (got[1] + got[2]) / max(sum(got), 1) <= 0.625


# sha256 (first 16 hex digits) of the three kernels (each pallas_call's own
# jaxpr and grid mapping) of a NON-causal flash call, forward and backward,
# on the parent of PR 35 (72361c6) at the tiles the dispatcher gave such a
# call then and gives it now: nothing to skip or trim, so the index maps
# stay bare and the kernels trace as they did. (A BTHD call whose kv sweep
# is ONE step, T 1024 here, takes the kernels' one-step path, causal or
# not, and is not among these.)
# PR 43 (parent 6dcd1d6): the calls its fused backward leaves alone hash as
# on ITS parent too: causal at T 2048, causal with Tk > T, causal BHTD, and a
# non-causal BTHD call of T 1024.
_PARENT_FLASH_FULL = {
    ("BTHD", 2048, 2048, 12, 64, False): "4b5986486b63f121", ("BHTD", 1024, 1024, 12, 64, False): "8a2d5f2b02b5f461",
    ("BTHD", 1024, 2048, 4, 128, False): "9809582472e40813",
    ("BTHD", 2048, 2048, 12, 64, True): "b4ad53e89584c5a1", ("BTHD", 1024, 2048, 12, 64, True): "323c667b7e11f2d6",
    ("BTHD", 1024, 1536, 4, 128, True): "3ff759509556c6f9", ("BHTD", 1024, 1024, 12, 64, True): "f99e4361ffac4b45",
    ("BHTD", 2048, 2048, 4, 128, True): "ab05d7a00a1f8e0b", ("BTHD", 1024, 1024, 12, 64, False): "8812b777696813f0"}


def _kernel_jaxprs(jaxpr):
    return [str(eqn.params["jaxpr"]) + str(eqn.params["grid_mapping"]) for eqn in _pallas_eqns(jaxpr)]


@pytest.mark.parametrize("layout,t,tk,h,d,causal", sorted(_PARENT_FLASH_FULL))
def test_a_non_causal_flash_call_runs_the_parents_kernels(monkeypatch, layout, t, tk, h, d, causal):
    from paddle_tpu.ops import attention

    for knob in ("PADDLE_TPU_FLASH_BLOCKS", "PADDLE_TPU_FLASH_BWD_BLOCKS"):
        monkeypatch.delenv(knob, raising=False)
    bq, bk, bwd = attention._flash_tiles(t, tk, layout, causal, heads=h, head_dim=d)

    def call(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk, layout=layout, bwd_blocks=bwd,
            interpret=False), q, k, v)
        return vjp(out)

    shape = lambda n: (2, n, h, d) if layout == "BTHD" else (2, h, n, d)  # noqa: E731
    q, kv = (jax.ShapeDtypeStruct(shape(n), jnp.bfloat16) for n in (t, tk))
    kernels = _kernel_jaxprs(jax.make_jaxpr(call)(q, kv, kv).jaxpr)
    assert len(kernels) == 3
    assert _sha("\n".join(kernels)) == _PARENT_FLASH_FULL[layout, t, tk, h, d, causal]


# The same programs for a block of another kind: OLMoE's (RMSNorm, RoPE, q/k
# norm, 64 experts of which a token takes 8, untied head) at the widths of
# the cell olmoe-serve-batch, 2 of its 12 layers, no weight allocated.


@pytest.fixture(scope="module")
def olmoe_programs(tpu_device):
    import os
    import sys
    import types

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import serve_compile_report as report

    dm = report.cell_model("olmoe-serve-batch", n_layer=2)
    out = types.SimpleNamespace(dm=dm, text={}, facts={})
    for name, (jit_fn, args) in report.serving_programs(dm).items():
        if name == "prefill_128":
            continue
        compiled = report.compile_on(jit_fn, args, tpu_device)
        out.text[name] = compiled.as_text()
        out.facts[name] = report.describe(compiled, dm.pool_shape())
    return out


@pytest.mark.parametrize("name,scopes", [
    ("decode_tick", ("embed", "jit(layer)/attn/qk_norm", "jit(layer)/attn/rope", "jit(layer)/attn/kv_write",
                     "jit(layer)/attn/paged", "jit(layer)/moe/route", "jit(layer)/moe/experts", "lm_head")),
    ("prefill_256", ("embed", "jit(layer)/attn/qk_norm", "jit(layer)/attn/rope", "jit(layer)/attn/kv_write",
                     "jit(layer)/attn/scores", "jit(layer)/moe/route", "jit(layer)/moe/experts", "lm_head"))])
def test_olmoe_programs_carry_their_names_and_scopes(olmoe_programs, name, scopes):
    from benchmark import manifest

    text = olmoe_programs.text[name]
    assert re.search(rf"HloModule jit_{name}\b", text)
    metric = "moe_decode_program_ms" if name == "decode_tick" else "moe_prefill_program_ms"
    assert re.search(manifest.layer_metric(metric)["args"]["pattern"], f"jit_{name}")
    ops = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in scopes:
        assert any(f"jit({name})/{scope}/" in o for o in ops), (scope, sorted(ops)[:20])
    assert not any("/mlp/" in o or "/attn/kv_gather/" in o for o in ops)


def test_olmoe_decode_tick_holds_one_paged_attention_kernel_a_layer(olmoe_programs):
    """16 heads of 128: a head's K and V are two aligned 128-lane tiles."""
    dm = olmoe_programs.dm
    assert dm.attention_path() == ("kernel", "")
    assert _kernel_names(olmoe_programs.text["decode_tick"]) == ["paged_attention"] * dm.cfg.n_layer
    assert _kernel_names(olmoe_programs.text["prefill_256"]) == []


@pytest.mark.parametrize("name", _SERVING_PROGRAMS)
def test_olmoe_pool_stays_in_place_unpadded_at_a_4096_lane_row(olmoe_programs, name):
    dm, facts = olmoe_programs.dm, olmoe_programs.facts[name]
    pool = facts["pool"]
    assert dm.pool_shape() == (2 * 960, 16, 4096)
    assert pool["parameter"] is not None and pool["aliased_to_output"], pool
    assert pool["layouts_in_program"] == [pool["layout"]], pool
    assert pool["layout"].startswith("2,1,0:T(8,128)"), pool
    assert facts["aliased_parameters"] == [pool["parameter"]]
    need = 2 * 960 * 16 * 4096 * 2
    assert abs(facts["memory"]["alias_size_in_bytes"] - need) <= 0.01 * need


@pytest.mark.parametrize("name", _SERVING_PROGRAMS)
def test_olmoe_program_moves_neither_context_nor_expert_weights(olmoe_programs, name):
    """No copy, reshape or transpose at the top level of anything as large
    as a layer's gathered context or one stacked expert weight."""
    dm = olmoe_programs.dm
    cfg = dm.cfg
    limit = min(dm.max_batch * dm.gather_len * cfg.d_model, cfg.n_experts * cfg.d_model * cfg.ffn_dim)
    big = [c for c in olmoe_programs.facts[name]["top_level_copies"] if c["elements"] >= limit]
    assert not big, big


@pytest.mark.parametrize("name", _SERVING_PROGRAMS)
def test_olmoe_temporaries_are_bounded_and_twelve_layers_fit_the_chip(olmoe_programs, name):
    """Temporaries: under one layer's expert activations alone (the decode
    tick gathers no context: 201 MB a layer before the kernel). The whole
    cell: 12 layers of weights, their pool, these temporaries (a layer's
    are reused by the next) and the code, under the chip's 16 GB with a
    tenth to spare."""
    from benchmark import arch, manifest

    dm, mem = olmoe_programs.dm, olmoe_programs.facts[name]["memory"]
    cfg = dm.cfg
    rows = dm.max_batch if name == "decode_tick" else 256
    activations = cfg.n_experts * rows * (2 * cfg.ffn_dim * 2 + cfg.d_model * (2 + 4))
    assert mem["temp_size_in_bytes"] < activations, (mem, activations)
    conf = manifest.cell(manifest.load(), "olmoe-serve-batch")["config"]
    weights = 2 * arch.of(conf).n_params(conf)
    pool = 12 * 960 * 16 * 4096 * 2
    total = weights + pool + mem["temp_size_in_bytes"] + mem["generated_code_size_in_bytes"] * 6
    assert total < 0.9 * 16e9, total


@pytest.mark.parametrize("programs,behind", [("serving_programs", 0), ("olmoe_programs", 3)])
def test_decode_tick_takes_a_slots_unread_token_from_the_tick_before(request, programs, behind):
    """The one-tick lookahead (serving/engine.py) costs the program one
    argument, the tick before's own second output (`behind`: the routing
    counts of a model with experts ride behind the tokens), and one
    `select` over the token ids; the pool's aliasing, layout and copies
    are held by the tests above, on this same program."""
    import serve_compile_report as report

    progs = request.getfixturevalue(programs)
    B = progs.dm.max_batch
    entry = report.entry_instructions(progs.text["decode_tick"])
    ints = sorted(i["dims"] for i in entry if i["op"] == "parameter" and i["dtype"] == "s32")
    # context lengths, tokens and `prev`, then the block tables
    assert ints == sorted([(B,), (B,), (B + behind,), (B, progs.dm.max_blocks_per_req)])
    assert re.search(r"pred\[%d\]\S* compare\(" % B, progs.text["decode_tick"])


# -- LFM2-MoE: layers of three kinds, two pools (tests/test_lfm2_serving.py) --

@pytest.fixture(scope="module")
def lfm2_programs(tpu_device):
    """The cell's decode tick and its bucket-256 prefill at the published
    widths, cut to the first four layers (conv + dense twice, attention +
    experts, conv + experts: every kind), compiled for the described chip."""
    import os
    import sys
    import types

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import serve_compile_report as report

    dm = report.cell_model("lfm2-serve-reason", n_layer=4)
    out = types.SimpleNamespace(dm=dm, text={}, facts={}, state={})
    for name, (jit_fn, args) in report.serving_programs(dm).items():
        if name == "prefill_128":
            continue
        compiled = report.compile_on(jit_fn, args, tpu_device)
        out.text[name] = compiled.as_text()
        out.facts[name] = report.describe(compiled, dm.pool_shape())
        out.state[name] = report.describe(compiled, dm.state_shape())["pool"]
    return out


@pytest.mark.parametrize("name,attends", [("decode_tick", "attn/paged"), ("prefill_256", "attn/scores")])
def test_lfm2_programs_carry_their_names_and_each_kinds_scopes(lfm2_programs, name, attends):
    from benchmark import manifest

    text = lfm2_programs.text[name]
    assert re.search(rf"HloModule jit_{name}\b", text)
    metric = "lfm2_decode_program_ms" if name == "decode_tick" else "lfm2_prefill_program_ms"
    assert re.search(manifest.layer_metric(metric)["args"]["pattern"], f"jit_{name}")
    ops = set(re.findall(r'op_name="([^"]*)"', text))
    conv = ("conv/in_proj", "conv/mix", "conv/state_write", "conv/out_proj")
    attn = ("attn/qk_norm", "attn/rope", "attn/kv_write", attends)
    moe = ("moe/route", "moe/experts")
    for layer, scopes in (("layer_conv_swiglu", conv + ("mlp",)), ("layer_attn_moe", attn + moe),
                          ("layer_conv_moe", conv + moe)):
        for scope in scopes:
            assert any(f"jit({name})/jit({layer})/{scope}/" in o for o in ops), (layer, scope)
    assert not any("/attn/kv_gather/" in o or "/jit(layer)/" in o for o in ops)


def test_lfm2_decode_tick_holds_one_grouped_query_kernel_an_attention_layer(lfm2_programs):
    """32 query heads over 8 K|V heads of 64: the kernel path, not the
    gathered window; the conv layers call no kernel."""
    dm = lfm2_programs.dm
    assert dm.attention_path() == ("kernel", "") and len(dm.attn_layers) == 1
    assert (dm.cfg.n_head, dm.cfg.kv_heads, dm.cfg.head_dim) == (32, 8, 64)
    assert _kernel_names(lfm2_programs.text["decode_tick"]) == ["paged_attention"]
    assert _kernel_names(lfm2_programs.text["prefill_256"]) == []
    facts = lfm2_programs.facts["decode_tick"]
    assert facts["mosaic_kernels"] == {"paged_attention": 1}


@pytest.mark.parametrize("name", _SERVING_PROGRAMS)
def test_lfm2_both_pools_stay_in_place_unpadded(lfm2_programs, name):
    """The KV pool is the attention layers' alone, 8 K|V heads a row; the
    state pool is a second donated array. Both alias their outputs, rest in
    the layout their updates work on, and nothing else is aliased."""
    dm, pool, state = lfm2_programs.dm, lfm2_programs.facts[name]["pool"], lfm2_programs.state[name]
    assert dm.pool_shape() == (1 * 10368, 16, 1024) and dm.state_shape() == (3, 2, 64, 2048)
    for p in (pool, state):
        assert p["parameter"] is not None and p["aliased_to_output"], p
        # one tiling throughout; the compiler may hold the small state pool
        # in fast memory between its layers (the `S(1)` of a layout)
        assert {re.sub(r"S\(\d\)$", "", lay) for lay in p["layouts_in_program"]} == {p["layout"]}, p
    assert pool["layouts_in_program"] == [pool["layout"]], pool
    assert pool["layout"].startswith("2,1,0:T(8,128)") and state["layout"].startswith("3,2,1,0:T(8,128)")
    assert lfm2_programs.facts[name]["aliased_parameters"] == sorted([pool["parameter"], state["parameter"]])
    need = (10368 * 16 * 1024 + 3 * 2 * 64 * 2048) * 2
    assert abs(lfm2_programs.facts[name]["memory"]["alias_size_in_bytes"] - need) <= 0.01 * need


@pytest.mark.parametrize("name", _SERVING_PROGRAMS)
def test_lfm2_program_moves_neither_pool_nor_expert_weights(lfm2_programs, name):
    cfg = lfm2_programs.dm.cfg
    limit = cfg.n_experts * cfg.d_model * cfg.ffn_dim  # one stacked expert weight; the pool is larger
    big = [c for c in lfm2_programs.facts[name]["top_level_copies"] if c["elements"] >= limit]
    assert not big, big


# -- the programs of the configurations the benchmark already had ------------

# sha256 (first 16 hex digits) of each program's StableHLO text as lowered
# for the described chip on the PARENT of the PR that gave layers kinds and
# the kernel grouped queries (ba29c55), with the Mosaic kernel's serialised
# body cut out (it carries source line numbers; the kernel's own jaxpr is
# held below instead). The LFM2 description's decode tick: on the parent of
# PR 48 (2eb2b6b). The prefills are PR 48's own: it gave each one more
# argument, the newest token vector on the device, and writes the prompt's
# first token into it (the decode ticks did not change for that; before it
# gpt2 / olmoe / lfm2 read 7b7f087b9f441263 / d4e27627cb07bdd5 /
# 9bcd9b55f481105f).
_PARENT_PROGRAMS = {
    ("gpt2", "decode_tick"): "cf354808014e7cc1", ("gpt2", "prefill_32"): "461e4d36e1e9524d",
    ("olmoe", "decode_tick"): "653b0a7cdc6d0601", ("olmoe", "prefill_32"): "2aa9785cfd6b45c9",
    ("lfm2", "decode_tick"): "a712444de7e22495", ("lfm2", "prefill_32"): "dc6ba1086558decc"}
_PARENT_KERNEL = {(4, 4, 64, 64, 8): "90ef1bd43a930eb2", (12, 25, 64, 864, 64): "9394cc5543d1440f",
                  (24, 16, 128, 1920, 64): "9c0520193a38207e"}


def _sha(text):
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.fixture(scope="module")
def lowered_small(tpu_device):
    """{(block, program): StableHLO text} of a small GPT-2, a small OLMoE
    and a small LFM2 description, lowered (not compiled) for the described
    chip."""
    import os
    import sys

    from paddle_tpu import serving

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import serve_compile_report as report

    sizes = dict(vocab_size=512, n_layer=2, d_model=256, max_seq_len=128, dtype="bfloat16")
    blocks = {"gpt2": serving.GPTConfig(n_head=4, **sizes),
              "olmoe": serving.GPTConfig(n_head=2, d_ff=64, tie_embeddings=False, norm="rmsnorm",
                                         position="rope", qk_norm=True, bias=False, mlp="moe",
                                         n_experts=8, experts_per_token=2, **sizes),
              "lfm2": serving.GPTConfig(n_head=16, n_kv_head=8, d_ff=64, d_ff_dense=96, tie_embeddings=True,
                                        norm="rmsnorm", position="rope", rope_theta=1e6, qk_norm="head",
                                        bias=False, mlp="moe", n_experts=8, experts_per_token=2,
                                        layer_ops=("conv", "attn", "conv"), layer_mlps=("swiglu", "moe", "moe"),
                                        router_score="sigmoid", router_bias=True, norm_topk=True,
                                        **dict(sizes, n_layer=3, d_model=1024))}
    out = {}
    for tag, cfg in blocks.items():
        dm = report.abstract_model(cfg, max_batch=4, n_blocks=32, block_size=16, prefill_buckets=[32])
        for name, (jit_fn, args) in report.serving_programs(dm).items():
            out[tag, name] = report.lower_on(jit_fn, args, tpu_device).as_text()
    return out


@pytest.mark.parametrize("block,program", sorted(_PARENT_PROGRAMS))
def test_programs_of_one_kind_of_layer_lower_as_on_the_parent(lowered_small, block, program):
    """A model of one kind of layer, one K|V head a query head and no conv
    state gets the decode tick it got before any of that existed: the same
    arguments, the same inner ``layer``, op for op; and no decode tick
    changed when an admission stopped emptying the device (PR 48): the
    first token is merged into `prev` by the PREFILL, one
    ``dynamic_update_slice`` at its end."""
    text = lowered_small[block, program]
    assert ("tpu_custom_call" in text) == (program == "decode_tick")
    merges = len(re.findall(r"dynamic_update_slice[^\n]*tensor<1xi32>", text))
    assert merges == (program != "decode_tick"), merges
    body_cut = re.sub(r'(\\22body\\22: \\22)[A-Za-z0-9+/=]+', r"\1", text)
    assert _sha(body_cut) == _PARENT_PROGRAMS[block, program]


@pytest.mark.parametrize("B,H,hd,rows,maxb", sorted(_PARENT_KERNEL))
def test_paged_attention_with_a_kv_head_a_query_head_is_the_parents_call(B, H, hd, rows, maxb):
    """``n_kv_head == n_head``: the kernel and the call around it trace to
    the jaxpr they traced to before grouped queries."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    def call(q, pool, tables, lens):
        return pa._paged_attention(q, pool, tables, lens, scale=0.125, interpret=False)

    jaxpr = jax.make_jaxpr(call)(
        jax.ShapeDtypeStruct((B, H, hd), jnp.bfloat16), jax.ShapeDtypeStruct((rows, 16, H * 2 * hd), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, maxb), jnp.int32), jax.ShapeDtypeStruct((B,), jnp.int32))
    assert _sha(str(jaxpr)) == _PARENT_KERNEL[B, H, hd, rows, maxb]
