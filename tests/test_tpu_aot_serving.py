"""Compile-only check of the serving programs (GPT-2 XL's, OLMoE's, LFM2's
and A.X-K1's decode tick and prefill, the paged-attention kernel in both its
modes, the embedding table at rest) against a real TPU target (tests/tpu_aot.py says how)."""
import functools
import math
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import serving
from tpu_aot import compiled_text, kernel_names, sha
from tpu_aot import tpu_arg, tpu_device, tpu_topology  # noqa: F401  (fixtures)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import serve_compile_report as report  # noqa: E402

_SERVING_PROGRAMS = ["decode_tick", "prefill_256"]


def _compiled_programs(dm, tpu_device):
    """``dm``'s decode tick and bucket-256 prefill compiled for the
    described chip through the model's own jit wrapper and pool
    description: ``.text``, ``.facts`` and, for a model with conv state,
    ``.state`` by program name (tools/serve_compile_report.py reads the
    facts)."""
    out = types.SimpleNamespace(dm=dm, text={}, facts={}, state={})
    for name, (jit_fn, args) in report.serving_programs(dm).items():
        if name not in _SERVING_PROGRAMS:
            continue
        compiled = report.compile_on(jit_fn, args, tpu_device)
        out.text[name] = compiled.as_text()
        out.facts[name] = report.describe(compiled, dm.pool_shape())
        if dm.state_shape() is not None:
            out.state[name] = report.describe(compiled, dm.state_shape())["pool"]
    return out


@pytest.mark.parametrize("B,H,hd,rows", [(12, 25, 64, 48 * 432), (24, 16, 128, 12 * 960)])
def test_paged_attention_compiles_at_both_serving_cells_shapes(tpu_arg, B, H, hd, rows):
    """The decode kernel alone, over a whole cell's pool (described, not
    allocated): a head of one 128-lane tile (GPT-2 XL) and of two (OLMoE)."""
    from paddle_tpu.ops.pallas.paged_attention import paged_attention

    text = compiled_text(
        functools.partial(paged_attention, scale=1.0 / math.sqrt(hd), interpret=False),
        tpu_arg((B, H, hd), jnp.bfloat16), tpu_arg((rows, 16, H * 2 * hd), jnp.bfloat16),
        tpu_arg((B, 64), jnp.int32), tpu_arg((B,), jnp.int32))
    assert kernel_names(text) == ["paged_attention"]
    # the pool goes to the kernel as it rests: no copy or relayout of it
    assert not re.search(rf"bf16\[{rows},16,{H * 2 * hd}\][^\n]* (copy|transpose|reshape)\(", text)


def _xl_serving_programs(tpu_device, vocab_size):
    """At the serving cells' widths (GPT-2 XL's heads, slots and blocks; 2
    layers, no weight allocated)."""
    cfg = serving.GPTConfig(vocab_size=vocab_size, n_layer=2, n_head=25, d_model=1600, max_seq_len=1024,
                            dtype="bfloat16")
    return _compiled_programs(
        report.abstract_model(cfg, max_batch=12, n_blocks=432, block_size=16, prefill_buckets=[256]), tpu_device)


@pytest.fixture(scope="module")
def serving_programs(tpu_device):
    """At a small vocabulary (1,024): what the pool's tests need."""
    return _xl_serving_programs(tpu_device, 1024)


@pytest.fixture(scope="module")
def xl_table_programs(tpu_device):
    """At GPT-2 XL's TRUE table, vocabulary 50,304: a copy of it shows."""
    return _xl_serving_programs(tpu_device, 50304)


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
    dm, facts = serving_programs.dm, serving_programs.facts
    assert facts["decode_tick"]["mosaic_kernels"] == {"paged_attention": dm.cfg.n_layer}
    assert facts["prefill_256"]["mosaic_kernels"] == {}
    att = report.attention_facts(dm, facts["decode_tick"]["mosaic_kernels"])
    assert att["decode_path"] == "kernel" and att["paged_attention_calls"] == 2
    # two buffers of 128 rows of 3,200 bf16 lanes, the float32 accumulator, two statistics
    assert att["step"] == {"positions": 128, "buffers": 2, "batched": False}
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
    assert kernel_names(serving_programs.text["decode_tick"]) == ["paged_attention"] * dm.cfg.n_layer
    assert kernel_names(serving_programs.text["prefill_256"]) == []


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

    text = compiled_text(lambda w: w + 1, tpu_arg(shape, jnp.dtype(dtype)))
    layout = re.search(r"entry_computation_layout=\{\(\w+\[[\d,]+\]\{([\d,]+):", text).group(1)
    assert layout == ("0,1" if rests_lanes_first(shape) else "1,0"), (shape, layout)


def _embed_facts(progs, name):
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


@pytest.fixture(scope="module")
def olmoe_programs(tpu_device):
    return _compiled_programs(report.cell_model("olmoe-serve-batch", n_layer=2), tpu_device)


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
    assert kernel_names(olmoe_programs.text["decode_tick"]) == ["paged_attention"] * dm.cfg.n_layer
    assert kernel_names(olmoe_programs.text["prefill_256"]) == []


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
    return _compiled_programs(report.cell_model("lfm2-serve-reason", n_layer=4), tpu_device)


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
    assert kernel_names(lfm2_programs.text["decode_tick"]) == ["paged_attention"]
    assert kernel_names(lfm2_programs.text["prefill_256"]) == []
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


# -- A.X-K1: latent attention over a latent pool (tests/test_axk1_serving.py) --

@pytest.fixture(scope="module")
def axk1_programs(tpu_device):
    """The cell's decode tick and its bucket-256 prefill at the published
    widths, cut to the first two layers (latent attention + dense, latent
    attention + 12 held experts and the shared one: both kinds), compiled
    for the described chip."""
    return _compiled_programs(report.cell_model("axk1-serve-reason", n_layer=2), tpu_device)


@pytest.mark.parametrize("name,attends", [("decode_tick", ("attn/absorb_q", "attn/paged", "attn/absorb_o")),
                                          ("prefill_256", ("attn/kv_expand", "attn/scores"))])
def test_axk1_programs_carry_their_names_and_each_forms_scopes(axk1_programs, name, attends):
    from benchmark import manifest

    text = axk1_programs.text[name]
    assert re.search(rf"HloModule jit_{name}\b", text)
    metric = "axk1_decode_program_ms" if name == "decode_tick" else "axk1_prefill_program_ms"
    assert re.search(manifest.layer_metric(metric)["args"]["pattern"], f"jit_{name}")
    ops = set(re.findall(r'op_name="([^"]*)"', text))
    latent = ("attn/q_lora", "attn/kv_latent", "attn/rope", "attn/kv_write") + attends
    for layer, scopes in (("layer_latent_swiglu", latent + ("mlp",)),
                          ("layer_latent_moe", latent + ("moe/route", "moe/experts", "moe/shared"))):
        for scope in scopes:
            assert any(f"jit({name})/jit({layer})/{scope}/" in o for o in ops), (layer, scope)
    assert not any("/attn/kv_gather/" in o or "/attn/qk_norm/" in o for o in ops)
    # the absorbed form is the decode tick's alone, the expanded form the prefill's
    other = ("attn/kv_expand",) if name == "decode_tick" else ("attn/absorb_q", "attn/absorb_o", "attn/paged")
    assert not any(f"/{scope}/" in o for o in ops for scope in other)


def test_axk1_decode_tick_holds_the_latent_kernel_once_a_layer(axk1_programs):
    """64 heads over ONE row of 640 lanes a position: the kernel's latent
    mode under its own name, not the gathered window and not the per-head
    kernel; the report says what the row holds."""
    dm = axk1_programs.dm
    assert dm.attention_path() == ("kernel", "") and dm.attn_layers == [0, 1] and dm.latent
    assert kernel_names(axk1_programs.text["decode_tick"]) == ["paged_latent_attention"] * 2
    assert kernel_names(axk1_programs.text["prefill_256"]) == []
    facts = axk1_programs.facts["decode_tick"]
    assert facts["mosaic_kernels"] == {"paged_latent_attention": 2}
    att = report.attention_facts(dm, facts["mosaic_kernels"])
    assert (att["decode_path"], att["kernel"], att["paged_attention_calls"]) == ("kernel", "paged_latent_attention", 2)
    assert att["row"] == {"lanes": 640, "holds": "latent | rotated key lanes | zeros", "latent": 512,
                          "rotated": 64, "zeros": 64, "heads_sharing_it": 64}
    # the step of a 1,280 B row: two buffers of 512 rows of 640 bf16 lanes; the float32 accumulator
    # of 64 heads over the row's 512 V lanes, two statistics
    assert att["step"] == {"positions": 512, "buffers": 2, "batched": True}
    assert att["vmem_scratch_bytes"] == 2 * 512 * 640 * 2 + 64 * 512 * 4 + 2 * 64 * 128 * 4 < 2 ** 21
    # a per-head model's report names its own kernel and row
    assert report.attention_facts(report.cell_model("olmoe-serve-batch", n_layer=1), {})["kernel"] == "paged_attention"


@pytest.mark.parametrize("name", _SERVING_PROGRAMS)
def test_axk1_latent_pool_is_donated_rests_row_major_and_is_never_copied(axk1_programs, name):
    dm, facts = axk1_programs.dm, axk1_programs.facts[name]
    pool = facts["pool"]
    assert dm.pool_shape() == (2 * 12032, 16, 640) and dm.state_shape() is None
    assert pool["parameter"] is not None and pool["aliased_to_output"], pool
    assert pool["layouts_in_program"] == [pool["layout"]] and pool["layout"].startswith("2,1,0:T(8,128)"), pool
    assert facts["aliased_parameters"] == [pool["parameter"]]
    need = 2 * 12032 * 16 * 640 * 2  # unpadded: 640 lanes are five whole tiles
    assert abs(facts["memory"]["alias_size_in_bytes"] - need) <= 0.01 * need
    # nothing as large as the pool or as one stacked weight of the held experts is copied or relaid
    limit = min(math.prod(dm.pool_shape()), 12 * 7168 * 2048)
    big = [c for c in facts["top_level_copies"] if c["elements"] >= limit]
    assert not big, big
    assert facts["memory"]["temp_size_in_bytes"] < 2 ** 30


def test_axk1_decode_tick_reads_four_routing_counts_behind_its_tokens(axk1_programs):
    entry = report.entry_instructions(axk1_programs.text["decode_tick"])
    ints = sorted(i["dims"] for i in entry if i["op"] == "parameter" and i["dtype"] == "s32")
    assert ints == sorted([(96,), (96,), (96 + 4,), (96, 128)])


def test_the_latent_kernel_compiles_alone_over_the_whole_cells_pool(tpu_arg):
    from paddle_tpu.ops.pallas.paged_attention import paged_latent_attention

    text = compiled_text(
        functools.partial(paged_latent_attention, scale=0.13, v_lanes=512, interpret=False),
        tpu_arg((96, 64, 576), jnp.bfloat16), tpu_arg((8 * 12032, 16, 640), jnp.bfloat16),
        tpu_arg((96, 128), jnp.int32), tpu_arg((96,), jnp.int32))
    assert kernel_names(text) == ["paged_latent_attention"]
    assert not re.search(r"bf16\[96256,16,640\][^\n]* (copy|transpose|reshape)\(", text)


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
# 9bcd9b55f481105f). PR 50 (latent attention, a shared expert, group-limited
# routing, an expert share) left all six as they were: every field it added
# to the block's description defaults to what these three blocks have.
_PARENT_PROGRAMS = {
    ("gpt2", "decode_tick"): "cf354808014e7cc1", ("gpt2", "prefill_32"): "461e4d36e1e9524d",
    ("olmoe", "decode_tick"): "653b0a7cdc6d0601", ("olmoe", "prefill_32"): "2aa9785cfd6b45c9",
    ("lfm2", "decode_tick"): "a712444de7e22495", ("lfm2", "prefill_32"): "dc6ba1086558decc"}
# The kernel's own jaxpr at rows that keep 128 positions a step (PR 51 gave the
# step to a rule over the row's bytes, ops/pallas/paged_attention.py::
# step_schedule: 6,400 and 8,192 B a position, the two cells' rows, and a small
# one of 5,120 B, whose hash is that of PR 51's parent, 8acd512; the 1,024 B row
# this table held before takes the longer, batched step and is the parent's no
# more: 90ef1bd43a930eb2 there).
_PARENT_KERNEL = {(4, 20, 64, 64, 8): "5fc05325cc7aaa5b", (12, 25, 64, 864, 64): "9394cc5543d1440f",
                  (24, 16, 128, 1920, 64): "9c0520193a38207e"}


@pytest.fixture(scope="module")
def lowered_small(tpu_device):
    """{(block, program): StableHLO text} of a small GPT-2, a small OLMoE
    and a small LFM2 description, lowered (not compiled) for the described
    chip."""
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
    assert sha(body_cut) == _PARENT_PROGRAMS[block, program]


@pytest.mark.parametrize("B,H,hd,rows,maxb", sorted(_PARENT_KERNEL))
def test_paged_attention_with_a_kv_head_a_query_head_is_the_parents_call(B, H, hd, rows, maxb):
    """``n_kv_head == n_head`` at a row that sits on its bytes: the kernel
    and the call around it trace to the jaxpr they traced to before grouped
    queries, before the latent mode and before the step followed from the
    row's bytes."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    def call(q, pool, tables, lens):
        return pa._paged_attention(q, pool, tables, lens, scale=0.125, interpret=False)

    jaxpr = jax.make_jaxpr(call)(
        jax.ShapeDtypeStruct((B, H, hd), jnp.bfloat16), jax.ShapeDtypeStruct((rows, 16, H * 2 * hd), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, maxb), jnp.int32), jax.ShapeDtypeStruct((B,), jnp.int32))
    assert sha(str(jaxpr)) == _PARENT_KERNEL[B, H, hd, rows, maxb]
