"""The readers PR 33 added for a model with conv layers and grouped-query
attention, each on a hand-built normalised trace and ledger document
against values worked out by hand; and what they return on a program
without such layers or such a kernel (the parent): nothing."""
import pytest

from benchmark import common, manifest
from benchmark.arch import lfm2_moe
from benchmark.readers import (conv_share, ledger_product_ratio, moe_decode_roofline, moe_experts_share,
                               op_share, paged_attention_roofline)

CONF = {"model_type": "lfm2_moe", "n_layer": 4, "n_head": 4, "n_embd": 8, "num_key_value_heads": 2,
        "intermediate_size": 12, "moe_intermediate_size": 4, "num_experts": 4, "num_experts_per_tok": 2,
        "vocab_size": 16, "layer_types": ["conv", "full_attention", "conv", "conv"], "num_dense_layers": 1,
        "conv_L_cache": 3, "n_expert_layers": 3}
TRAFFIC = {"engine": {"max_batch": 3, "n_blocks": 11, "block_size": 4}}
# two ticks of 3 live slots: 3 expert layers x 3 slots x 2 experts = 18 pairs a tick
LEDGER = {"decode_ticks": 2, "moe_assignments": 36, "moe_experts_hit": 21, "moe_max_load": 12}
MODULES = [["jit_decode_tick", 0.0, 4000.0, "jit_decode_tick(1)"],
           ["jit_prefill_128", 5000.0, 9000.0, "jit_prefill_128(2)"],
           ["jit_decode_tick", 20000.0, 6000.0, "jit_decode_tick(1)"]]


def _ctx(trace=None, config=CONF, peaks=None):
    ctx = common.Ctx(cell={"name": "hand-built", "chips": 1, "config": config, "traffic": TRAFFIC},
                     seed=0, seconds=1.0, trace=True, rehearse=False, devices=[], peaks=peaks, t0=0.0)
    ctx.norm_trace = trace
    # half of the 10 usable blocks of 4 tokens in use: 20 live tokens
    ctx.counters.update({"ledger.kv_util_weight": 0.5, "ledger.weighted_wall": 1.0})
    return ctx


def _args(metric):
    return manifest.layer_metric(metric).get("args", {})


def test_bytes_by_hand():
    # one attention layer, 2 K|V heads of 2 lanes: 2 x 2 x 2 x 2 B = 16 B a token
    assert lfm2_moe.kv_token_bytes(CONF) == 16
    assert lfm2_moe.paged_attention_bytes(CONF, 20.0) == 320.0
    # three conv layers x 2 gated inputs x 3 slots x 8 lanes x 2 B, read and written
    assert lfm2_moe.state_bytes(CONF, 3) == 288
    parts = lfm2_moe.decode_tick_bytes(CONF, slots=3, live_kv_tokens=20.0, experts_hit=10.5)
    assert parts["experts"] == 10.5 * 3 * 8 * 4 * 2 and parts["kv"] == 320.0 and parts["state"] == 576
    # embedding 128 + final gain 8; conv layer: 192 + 24 + 64 = 280, attention layer: 64 + 32 + 32 + 64
    # + 2 + 2 = 196; two gains a layer; dense 3 x 96; router 32 + bias 4 a layer with experts
    other = 128 + 8 + 3 * 280 + 196 + 4 * 16 + 288 + 3 * 36
    assert parts["other_weights"] == other * 2
    assert lfm2_moe.n_params(CONF) == other + 3 * 4 * 96
    assert lfm2_moe.conv_shapes(CONF) == {"[8,24]": 1.0, "[3,8]": 1.0, "[8,8]": 3 / 5}


def test_experts_hit_counts_the_expert_layers_alone(monkeypatch):
    from paddle_tpu.serving import ledger

    monkeypatch.setattr(ledger, "totals", lambda: dict(LEDGER))
    # 21 (layer, expert) pairs hit in 2 ticks of 3 expert layers x 4 experts = 24
    assert ledger_product_ratio.read(_ctx(), _args("lfm2_experts_hit_pct")) == pytest.approx(100 * 21 / 24)
    assert ledger_product_ratio.read(_ctx(), _args("lfm2_load_max_over_mean")) == pytest.approx(12 / 9)
    trace = {"devices": {"d0": []}, "host": [], "modules": {"d0": MODULES}}
    ctx = _ctx(trace, peaks={"hbm_bytes_per_s": 1e9})
    need = sum(lfm2_moe.decode_tick_bytes(CONF, 3, 20.0, 10.5).values())
    assert moe_decode_roofline.read(ctx, {}) == pytest.approx(100.0 * (need / 1e9) / 5e-6)
    assert set(ctx.results["moe_decode_program"]["bytes_by_part"]) == {"experts", "other_weights", "kv", "state"}


def test_conv_share_finds_operations_by_the_weights_they_read():
    # five slots: an activation is [5,8], the taps [3,8]
    in_proj = "%fusion.1 = bf16[5,24]{1,0} fusion(bf16[5,8]{1,0} %x, bf16[8,24]{1,0} %w_in), kind=kOutput"
    mix = "%fusion.2 = bf16[5,8]{1,0} fusion(bf16[5,24]{1,0} %bcx, bf16[3,8]{1,0} %taps, bf16[3,2,5,8]{3,2,1,0} %state)"
    square = "%fusion.3 = bf16[5,8]{1,0} fusion(bf16[5,8]{1,0} %y, bf16[8,8]{1,0} %w), kind=kOutput"
    experts = "%fusion.4 = bf16[4,5,4]{2,1,0} fusion(bf16[5,8]{1,0} %x, bf16[4,8,4]{2,1,0} %gate), kind=kOutput"
    evs = [["fusion.1", 0.0, 200.0, in_proj], ["fusion.2", 200.0, 100.0, mix], ["fusion.3", 300.0, 100.0, square],
           ["fusion.4", 400.0, 600.0, experts]]
    ctx = _ctx({"devices": {"d0": evs}, "modules": {}, "host": []})
    # the input projection and the taps whole; three fifths of the [8,8] matmul, which the
    # attention layer's q and output projections share with the three conv layers' out-projections
    assert conv_share.read(ctx, {}) == pytest.approx(100.0 * (200 + 100 + 0.6 * 100) / 1000.0)
    assert sum(v["events"] for v in ctx.results["conv_ops"].values()) == 3
    assert moe_experts_share.read(ctx, {}) == pytest.approx(60.0)
    # a trace without such operations; an architecture without conv layers; no architecture at all
    assert conv_share.read(_ctx({"devices": {"d0": [evs[3]]}, "modules": {}, "host": []}), {}) is None
    assert conv_share.read(_ctx({"devices": {"d0": evs}}, config={"model_type": "olmoe"}), {}) is None
    assert conv_share.read(_ctx({"devices": {"d0": evs}}, config={"n_embd": 8}), {}) is None


def _kernel_trace():
    call = ('%paged_attention.{n} = f32[3,2,8]{{2,1,0}} custom-call(s32[9]{{0}} %t, s32[3]{{0}} %l, f32[3,2,8]{{2,1,0}} %q, '
            'bf16[11,4,8]{{2,1,0}} %pool), custom_call_target="tpu_custom_call"')
    other = "%fusion.9 = bf16[3,8]{1,0} fusion(bf16[3,8]{1,0} %x), kind=kLoop"
    evs = [["paged_attention.1", 100.0, 300.0, call.format(n=1)], ["fusion.9", 400.0, 3000.0, other],
           ["paged_attention.2", 20100.0, 500.0, call.format(n=2)], ["fusion.9", 20600.0, 200.0, other]]
    return {"devices": {"d0": evs}, "host": [], "modules": {"d0": MODULES}}


def test_attention_share_and_the_kernels_roofline():
    ctx = _ctx(_kernel_trace(), peaks={"hbm_bytes_per_s": 1e9})
    assert op_share.read(ctx, _args("lfm2_attention_share_pct")) == pytest.approx(100.0 * 800 / 4000)
    assert ctx.results["op_share"]["^paged_attention"]["events"] == 2
    # 320 B of live K and V a tick at 1 GB/s = 320 ns; the kernel took 800 ns over 2 ticks
    assert paged_attention_roofline.read(ctx, {}) == pytest.approx(100.0 * 320e-9 / 400e-9)
    fact = ctx.results["paged_attention_kernel"]
    assert (fact["ticks_in_slice"], fact["events_a_tick"], fact["live_kv_tokens"]) == (2, 1.0, 20.0)


def test_the_kernels_readers_find_nothing_where_there_is_no_kernel_or_no_byte_count():
    gathered = _kernel_trace()
    gathered["devices"]["d0"] = [e for e in gathered["devices"]["d0"] if e[0] == "fusion.9"]
    peaks = {"hbm_bytes_per_s": 1e9}
    assert op_share.read(_ctx(gathered), _args("lfm2_attention_share_pct")) is None
    assert paged_attention_roofline.read(_ctx(gathered, peaks=peaks), {}) is None
    assert paged_attention_roofline.read(_ctx(_kernel_trace(), config={"model_type": "olmoe"}, peaks=peaks), {}) is None
    assert paged_attention_roofline.read(_ctx(_kernel_trace(), config={"n_embd": 8}, peaks=peaks), {}) is None
    assert paged_attention_roofline.read(_ctx(None, peaks=peaks), {}) is None and op_share.read(_ctx(None), {}) is None


def test_the_cells_metrics_are_in_the_manifest_with_their_readers():
    man = manifest.load()
    cell = manifest.cell(man, "lfm2-serve-reason")
    own = [m for m in cell["per_layer"] if "workloads" in m]  # the rest hold in every cell (PR 36: setup_*_s)
    names = [m["name"] for m in own]
    every = [m["name"] for m in man["per_layer"]]
    assert names == every[every.index(names[0]):][:len(names)]  # appended at the end, as one run, by PR 33
    assert len(names) == 15 and all(n.startswith("lfm2_") for n in names)
    assert {m["moves"] for m in own} == {"serve_tokens_per_s"}
    assert {m["moves"] for m in cell["per_layer"] if m not in own} == {"setup_s"}
    assert [m["name"] for m in cell["end_to_end"]] == ["serve_tokens_per_s", "setup_s"]
    for n in names:
        spec = manifest.layer_metric(n)
        assert spec["workloads"] == ["lfm2-serve-reason"]
        if n.endswith("_roofline") or n.endswith("_pct"):
            assert spec["unit"] == "%"
    assert man["workloads"][5]["name"] == "lfm2-serve-reason" and man["configs"][3]["name"] == "lfm2-24b-a2b"
    assert manifest.problems(man) == []
    # the traffic file holds the cell's parameters and no others
    tr = cell["traffic"]
    assert (tr["kind"], tr["loop"], tr["clients"], tr["stratified"], tr["requests_per_client"]) == (
        "serve_arch", "closed", 64, True, 8)
    assert tr["prompt_len"] == {"dist": "uniform", "lo": 64, "hi": 256} and tr["max_total"] == 2600
    assert tr["output_len"] == {"dist": "loguniform", "lo": 1152, "hi": 1728}  # the issue's fallback: PERF.md section 6
    assert tr["engine"] == {"max_batch": 64, "block_size": 16, "n_blocks": 10368, "prefill_buckets": [128, 256],
                            "window": 3072, "dtype": "bfloat16"}
    assert (tr["tokens"], tr["deadline_s"], tr["trace_seconds"]) == ({"dist": "zipf", "a": 1.1}, 600.0, 2.0)
