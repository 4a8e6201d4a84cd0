"""The readers PR 26 added for a model with experts, each on a hand-built
normalised trace and ledger document against values worked out by hand;
and what they return on a program without experts (the parent): nothing."""
import pytest

from benchmark import common, manifest
from benchmark.arch import olmoe
from benchmark.readers import ledger_product_ratio, moe_decode_roofline, moe_experts_share

CONF = {"model_type": "olmoe", "n_layer": 2, "n_head": 2, "n_embd": 8, "intermediate_size": 4,
        "num_experts": 4, "num_experts_per_tok": 2, "vocab_size": 16}
TRAFFIC = {"engine": {"max_batch": 3, "n_blocks": 11, "block_size": 4}}
# two ticks of 3 live slots: 2 layers x 3 slots x 2 experts = 12 pairs a tick
LEDGER = {"decode_ticks": 2, "moe_assignments": 24, "moe_experts_hit": 13, "moe_max_load": 10}


def _ctx(trace=None, config=CONF, peaks=None):
    ctx = common.Ctx(cell={"name": "hand-built", "chips": 1, "config": config, "traffic": TRAFFIC},
                     seed=0, seconds=1.0, trace=True, rehearse=False, devices=[], peaks=peaks, t0=0.0)
    ctx.norm_trace = trace
    return ctx


def _args(metric):
    return manifest.layer_metric(metric).get("args", {})


@pytest.fixture
def ledger_doc(monkeypatch):
    from paddle_tpu.serving import ledger

    monkeypatch.setattr(ledger, "totals", lambda: dict(LEDGER))


def test_counter_metrics_on_the_ledger_document(ledger_doc):
    # 13 (layer, expert) pairs hit in 2 ticks of 2 layers x 4 experts = 16
    assert ledger_product_ratio.read(_ctx(), _args("moe_experts_hit_pct")) == pytest.approx(100 * 13 / 16)
    # busiest experts' loads sum to 10; the mean expert's to 24 / 4 = 6
    assert ledger_product_ratio.read(_ctx(), _args("moe_load_max_over_mean")) == pytest.approx(10 / 6)


def test_counter_metrics_find_nothing_without_routing_counters(monkeypatch):
    from paddle_tpu.serving import ledger

    monkeypatch.setattr(ledger, "totals", lambda: {"decode_ticks": 5, "tick_wall_s": 1.0})  # PR 25's keys
    assert ledger_product_ratio.read(_ctx(), _args("moe_experts_hit_pct")) is None
    assert ledger_product_ratio.read(_ctx(), _args("moe_load_max_over_mean")) is None
    monkeypatch.undo()
    ledger.reset()  # the real ledger before any tick: zero ticks, no division
    assert ledger_product_ratio.read(_ctx(), _args("moe_experts_hit_pct")) is None
    assert moe_decode_roofline.read(_ctx({"devices": {}, "modules": {}}, peaks={"hbm_bytes_per_s": 1.0}), {}) is None


def test_decode_tick_bytes_by_hand():
    # one expert: 3 x 8 x 4 x 2 B = 192 B; 6.5 hit a tick
    parts = olmoe.decode_tick_bytes(CONF, slots=3, live_kv_tokens=20.0, experts_hit=6.5)
    assert parts["experts"] == 6.5 * 192
    # a layer: 4 x 64 attention + 4 x 8 gains + 8 x 4 router = 320; head 128, final gain 8, 3 rows of 8
    assert parts["other_weights"] == (2 * 320 + 128 + 8 + 24) * 2
    assert parts["kv"] == 2 * 2 * 8 * 2 * 20.0


def test_decode_roofline_on_a_hand_built_trace(ledger_doc):
    trace = {"devices": {"d0": []}, "host": [],
             "modules": {"d0": [["jit_decode_tick", 0.0, 4000.0, "jit_decode_tick(1)"],
                                ["jit_prefill_128", 5000.0, 9000.0, "jit_prefill_128(2)"],
                                ["jit_decode_tick", 20000.0, 6000.0, "jit_decode_tick(1)"],
                                ["jit_decode_tick", 30000.0, 5000.0, "jit_decode_tick(1)"]]}}
    ctx = _ctx(trace, peaks={"hbm_bytes_per_s": 1e9})
    # half of the 10 usable blocks of 4 tokens in use: 20 live tokens
    ctx.counters.update({"ledger.kv_util_weight": 0.5, "ledger.weighted_wall": 1.0})
    need = sum(olmoe.decode_tick_bytes(CONF, 3, 20.0, 6.5).values())
    got = moe_decode_roofline.read(ctx, {})
    assert got == pytest.approx(100.0 * (need / 1e9) / 5e-6)  # the median tick: 5000 ns
    fact = ctx.results["moe_decode_program"]
    assert fact["runs_in_slice"] == 3 and fact["experts_hit_a_tick"] == 6.5
    assert fact["live_kv_tokens"] == pytest.approx(20.0) and fact["bytes_needed"] == pytest.approx(need)
    # a configuration with no architecture module (GPT-2's files): nothing to read
    assert moe_decode_roofline.read(_ctx(trace, config={"n_embd": 8}, peaks={"hbm_bytes_per_s": 1e9}), {}) is None


def test_experts_share_finds_operations_by_the_stacked_weights_shape():
    gate = "%fusion.1 = bf16[4,3,4]{2,1,0} fusion(bf16[3,8]{1,0} %x, bf16[4,8,4]{2,1,0} %gate), kind=kOutput"
    down = "%fusion.2 = bf16[4,3,8]{2,1,0} fusion(bf16[4,3,4]{2,1,0} %h, bf16[4,4,8]{2,1,0} %down), kind=kOutput"
    feed = "%copy.3 = bf16[4,8,4]{1,2,0} copy(bf16[4,8,4]{2,1,0} %gate)"
    attn = "%fusion.4 = bf16[3,2,4]{2,1,0} fusion(bf16[3,8]{1,0} %x), kind=kLoop"
    evs = [["fusion.1", 0.0, 300.0, gate], ["fusion.4", 300.0, 500.0, attn], ["copy.3", 900.0, 100.0, feed],
           ["fusion.2", 1000.0, 100.0, down]]
    ctx = _ctx({"devices": {"d0": evs}, "modules": {}, "host": []})
    assert olmoe.expert_shapes(CONF) == ["[4,8,4]", "[4,4,8]"]
    assert moe_experts_share.read(ctx, {}) == pytest.approx(100.0 * 500.0 / 1000.0)
    assert sum(v["events"] for v in ctx.results["moe_experts_ops"].values()) == 3
    # a dense model's trace, or a cell whose configuration has no architecture module
    assert moe_experts_share.read(_ctx({"devices": {"d0": [evs[1]]}, "modules": {}, "host": []}), {}) is None
    assert moe_experts_share.read(_ctx({"devices": {"d0": evs}}, config={"n_embd": 8}), {}) is None


def test_the_cells_metrics_are_in_the_manifest_with_their_readers():
    man = manifest.load()
    cell = manifest.cell(man, "olmoe-serve-batch")
    own = [m for m in cell["per_layer"] if "workloads" in m]  # the rest hold in every cell (PR 36: setup_*_s)
    names = [m["name"] for m in own]
    every = [m["name"] for m in man["per_layer"]]  # appended at the end, as one run, by PR 26
    assert names == every[every.index(names[0]):][:len(names)]
    assert len(names) == 12 and all(n.startswith("moe_") for n in names)
    assert {m["moves"] for m in own} == {"serve_tokens_per_s"}
    assert {m["moves"] for m in cell["per_layer"] if m not in own} == {"setup_s"}
    assert [m["name"] for m in cell["end_to_end"]] == ["serve_tokens_per_s", "setup_s"]
    for n in names:
        assert manifest.layer_metric(n)["workloads"] == ["olmoe-serve-batch"]
    assert manifest.layer_metric("moe_decode_program_ms")["args"] == manifest.layer_metric("decode_program_ms")["args"]
    assert [w["chips"] for w in man["workloads"]].count(4) == 1 and len(man["workloads"]) == 7
