"""The readers and counts PR 50 added for a model with latent attention and
a share of its router's experts, each on a hand-built normalised trace and
ledger document against values worked out by hand; and what they return on a
program without such a kernel or counter (the parent): nothing."""
import pytest

from benchmark import common, manifest
from benchmark.arch import axk1
from benchmark.readers import (latent_attention_roofline, ledger_product_ratio, moe_decode_roofline,
                               moe_experts_share, op_share)

CONF = {"model_type": "axk1", "n_layer": 3, "n_head": 4, "n_embd": 8, "vocab_size": 16, "first_k_dense_replace": 1,
        "intermediate_size": 12, "moe_intermediate_size": 4, "n_routed_experts": 2, "router_experts": 16,
        "first_expert_held": 2, "n_shared_experts": 1, "num_experts_per_tok": 4, "q_lora_rank": 6,
        "kv_lora_rank": 10, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2, "v_head_dim": 3, "n_expert_layers": 2}
TRAFFIC = {"engine": {"max_batch": 3, "n_blocks": 11, "block_size": 4}}
# two ticks of 3 live slots: 2 expert layers x 3 slots x 4 experts = 24 pairs routed a tick, 3 of them held
LEDGER = {"decode_ticks": 2, "moe_assignments": 6, "moe_assignments_routed": 48, "moe_experts_hit": 5,
          "moe_max_load": 4}
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


def test_bytes_and_operations_by_hand():
    # a position's row as it is used: 10 latent + 2 rotated lanes x 2 B, in each of 3 layers
    assert axk1.kv_token_bytes(CONF) == 3 * 12 * 2 == 72
    assert axk1.latent_attention_bytes(CONF, 20.0) == 1440.0
    # 4 heads score 12 lanes and weigh 10, a multiply and an add each, 3 layers, 20 positions
    assert axk1.latent_attention_flops(CONF, 20.0) == 2 * 4 * 22 * 3 * 20
    parts = axk1.decode_tick_bytes(CONF, slots=3, live_kv_tokens=20.0, experts_hit=3.5)
    assert parts["experts"] == 3.5 * 3 * 8 * 4 * 2 and parts["kv"] == 1440.0 and set(parts) == {"experts", "other_weights", "kv"}
    # attention: 8x6 + 6 + 6x(4x6) + 8x12 + 10 + 10x(4x7) + (4x3)x8 = 680; two gains a layer
    attn = 48 + 6 + 144 + 96 + 10 + 280 + 96
    assert attn == 680
    # dense 3 x 96; an expert layer beside its 2 held experts: router 8 x 16, shared 3 x 32
    layers = 3 * (attn + 16) + 288 + 2 * (128 + 96)
    # the head 128 and the final gain 8 whole, of the embedding one row a slot (3 x 8), not its 128
    assert parts["other_weights"] == (layers + 128 + 8 + 3 * 8) * 2
    assert axk1.n_params(CONF) == layers + 128 + 8 + 128 + 2 * 2 * 96
    assert axk1.expert_shapes(CONF) == ["[2,8,4]", "[2,4,8]"]


def test_the_ledgers_ratios_count_the_held_experts(monkeypatch):
    from paddle_tpu.serving import ledger

    monkeypatch.setattr(ledger, "totals", lambda: dict(LEDGER))
    # 5 (layer, held expert) pairs hit in 2 ticks of 2 expert layers x 2 held experts = 8
    assert ledger_product_ratio.read(_ctx(), _args("axk1_experts_hit_pct")) == pytest.approx(100 * 5 / 8)
    # the busiest held expert took 4 of 6 assignments over 2 held experts: 4 x 2 / 6
    assert ledger_product_ratio.read(_ctx(), _args("axk1_load_max_over_mean")) == pytest.approx(4 / 3)
    # 6 of the 48 routed assignments landed on the held experts; even routing would give 2 / 16
    assert ledger_product_ratio.read(_ctx(), _args("axk1_held_assignment_pct")) == pytest.approx(12.5)
    trace = {"devices": {"d0": []}, "host": [], "modules": {"d0": MODULES}}
    ctx = _ctx(trace, peaks={"hbm_bytes_per_s": 1e9})
    need = sum(axk1.decode_tick_bytes(CONF, 3, 20.0, 2.5).values())
    assert moe_decode_roofline.read(ctx, {}) == pytest.approx(100.0 * (need / 1e9) / 5e-6)
    assert set(ctx.results["moe_decode_program"]["bytes_by_part"]) == {"experts", "other_weights", "kv"}
    # a program whose ledger has no such counter (the parent), or a model that holds every expert (0 routed)
    monkeypatch.setattr(ledger, "totals", lambda: {k: v for k, v in LEDGER.items() if k != "moe_assignments_routed"})
    assert ledger_product_ratio.read(_ctx(), _args("axk1_held_assignment_pct")) is None
    monkeypatch.setattr(ledger, "totals", lambda: dict(LEDGER, moe_assignments_routed=0))
    assert ledger_product_ratio.read(_ctx(), _args("axk1_held_assignment_pct")) is None


def _kernel_trace(name="paged_latent_attention"):
    call = ('%{name}.{n} = f32[3,16,10]{{2,1,0}} custom-call(s32[9]{{0}} %t, s32[3]{{0}} %l, bf16[3,16,128]{{2,1,0}} %q, '
            'bf16[33,4,128]{{2,1,0}} %pool), custom_call_target="tpu_custom_call"')
    other = "%fusion.9 = bf16[3,8]{1,0} fusion(bf16[3,8]{1,0} %x, bf16[2,8,4]{2,1,0} %gate), kind=kLoop"
    evs = [[f"{name}.1", 100.0, 300.0, call.format(name=name, n=1)], ["fusion.9", 400.0, 3000.0, other],
           [f"{name}.2", 20100.0, 500.0, call.format(name=name, n=2)], ["fusion.9", 20600.0, 200.0, other]]
    return {"devices": {"d0": evs}, "host": [], "modules": {"d0": MODULES}}


def test_the_latent_kernels_share_and_its_roofline_take_the_larger_bound():
    peaks = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    ctx = _ctx(_kernel_trace(), peaks=peaks)
    assert op_share.read(ctx, _args("axk1_attention_share_pct")) == pytest.approx(100.0 * 800 / 4000)
    assert ctx.results["op_share"]["^paged_latent_attention"]["events"] == 2
    assert moe_experts_share.read(ctx, {}) == pytest.approx(100.0 * 3200 / 4000)  # what reads the held stack
    # 1,440 B a tick at 1 GB/s = 1,440 ns; 10,560 operations at 1 TFLOP/s = 10.56 ns: bound by bytes.
    # The kernel took 800 ns over 2 ticks, so the hand-built trace reads over 100 (no clamp hides it)
    assert latent_attention_roofline.read(ctx, {}) == pytest.approx(100.0 * 1440e-9 / 400e-9)
    fact = ctx.results["latent_attention_kernel"]
    assert (fact["ticks_in_slice"], fact["events_a_tick"], fact["live_kv_tokens"], fact["bound"]) == (2, 1.0, 20.0, "memory")
    # a slower matrix unit makes the operations the larger bound
    slow = _ctx(_kernel_trace(), peaks=dict(peaks, bf16_flops_per_s=1e9))
    assert latent_attention_roofline.read(slow, {}) == pytest.approx(100.0 * 10560e-9 / 400e-9)
    assert slow.results["latent_attention_kernel"]["bound"] == "compute"


def test_the_two_kernels_are_told_apart_and_a_program_without_one_reads_nothing():
    peaks = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    per_head = _kernel_trace("paged_attention")  # the parent's kernel, another model's
    assert op_share.read(_ctx(per_head), _args("axk1_attention_share_pct")) is None
    assert latent_attention_roofline.read(_ctx(per_head, peaks=peaks), {}) is None
    assert op_share.read(_ctx(_kernel_trace()), _args("lfm2_attention_share_pct")) is None
    assert latent_attention_roofline.read(_ctx(_kernel_trace(), config={"model_type": "olmoe"}, peaks=peaks), {}) is None
    assert latent_attention_roofline.read(_ctx(_kernel_trace(), config={"n_embd": 8}, peaks=peaks), {}) is None
    assert latent_attention_roofline.read(_ctx(None, peaks=peaks), {}) is None
    assert latent_attention_roofline.read(_ctx(_kernel_trace()), {}) is None  # no peaks: a rehearsal


def test_the_cells_metrics_are_in_the_manifest_with_their_readers():
    man = manifest.load()
    cell = manifest.cell(man, "axk1-serve-reason")
    own = [m for m in cell["per_layer"] if "workloads" in m]  # the rest hold in every cell (PR 36: setup_*_s)
    names = [m["name"] for m in own]
    every = [m["name"] for m in man["per_layer"]]
    assert names == every[-len(names):]  # appended at the end, as one run, by PR 50
    assert len(names) == 15 and all(n.startswith("axk1_") for n in names)
    assert {m["moves"] for m in own} == {"serve_tokens_per_s"}
    assert [m["name"] for m in cell["end_to_end"]] == ["serve_tokens_per_s", "setup_s"]
    for n in names:
        spec = manifest.layer_metric(n)
        assert spec["workloads"] == ["axk1-serve-reason"]
        if n.endswith("_roofline") or n.endswith("_pct"):
            assert spec["unit"] == "%"
    assert man["workloads"][-1]["name"] == "axk1-serve-reason" and man["configs"][-1]["name"] == "ax-k1"
    assert "16x its share" in man["workloads"][-1]["why"] and len(man["workloads"][-1]["why"]) <= 200
    assert man["configs"][-1]["reduced"] == ["n_layer", "num_hidden_layers", "n_routed_experts", "vocab_size"]
    serve = next(m for m in man["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert serve["workloads"][-1] == "axk1-serve-reason" and serve["bound"] == 0.025
    assert manifest.problems(man) == []
    tr = cell["traffic"]
    assert (tr["kind"], tr["loop"], tr["clients"], tr["stratified"], tr["requests_per_client"]) == (
        "serve_arch", "closed", 96, True, 8)
    assert tr["prompt_len"] == {"dist": "uniform", "lo": 64, "hi": 256} and tr["max_total"] == 2000
    assert tr["output_len"] == {"dist": "loguniform", "lo": 1152, "hi": 1728}
    assert tr["engine"] == {"max_batch": 96, "block_size": 16, "n_blocks": 12032, "prefill_buckets": [128, 256],
                            "window": 2048, "dtype": "bfloat16"}
    assert (tr["tokens"], tr["deadline_s"], tr["trace_seconds"]) == ({"dist": "zipf", "a": 1.1}, 600.0, 2.0)


def test_the_catalogs_numbers_stand_in_the_configuration_file_unless_reduced():
    """Every number of the published config is in the file under its key;
    only the keys under 'reduced' differ, and no width is among them."""
    published = {"ep_size": 1, "first_k_dense_replace": 1, "hidden_size": 7168, "intermediate_size": 18432,
                 "kv_lora_rank": 512, "max_position_embeddings": 131072, "moe_intermediate_size": 2048,
                 "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 192, "n_shared_experts": 1,
                 "num_attention_heads": 64, "num_experts_per_tok": 8, "num_hidden_layers": 61,
                 "num_key_value_heads": 64, "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "rms_norm_eps": 1e-06, "rope_theta": 10000, "routed_scaling_factor": 2.5, "topk_group": 4,
                 "v_head_dim": 128, "vocab_size": 163840}
    c = manifest.cell(manifest.load(), "axk1-serve-reason")["config"]
    differ = {k for k, v in published.items() if c[k] != v}
    assert differ == {"n_routed_experts", "num_hidden_layers", "vocab_size"} and differ <= set(c["reduced"])
    assert all(c["published"][k] == published[k] for k in differ)
    assert c["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
                                 "original_max_position_embeddings": 4096, "type": "yarn"}
    assert (c["scoring_func"], c["topk_method"], c["norm_topk_prob"], c["tie_word_embeddings"], c["seq_aux"],
            c["attention_bias"], c["hidden_act"], c["model_type"]) == (
        "sigmoid", "none", True, False, True, False, "silu", "axk1")
    assert (c["n_routed_experts"], c["num_hidden_layers"], c["n_layer"], c["vocab_size"]) == (12, 8, 8, 20480)
    for key in ("router", "rope_pairing", "yarn", "initialisation", "dtype"):
        assert key in c["assumed"]
    assert "sixteen chips" in c["deployment"] and "11.03 GB" in c["bytes"]
