"""The LFM2-MoE block (gated short convolutions with per-slot state beside
grouped-query attention over a KV pool only the attention layers own, two
kinds of feed-forward, sigmoid top-k routing under a selection bias, tied
head) served by the ONE DecodeModel, against the plain float32 reference
in benchmark/reference/lfm2_moe.py: tiny widths, CPU."""
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import arch as arch_modules
from benchmark.reference import lfm2_moe as reference
from paddle_tpu import serving
from paddle_tpu.serving import ledger
from paddle_tpu.serving.model import param_table

F, FD, E, K, V = 32, 48, 8, 2, 256
TYPES = ("conv", "full_attention", "conv", "conv")  # one dense layer, then experts
TOL = 1e-4  # float32 against float32: rounding and summation order only
# (hidden, query heads, K|V heads): a head of 16 lanes takes the gathered
# window, 16 query heads of 64 over 8 K|V heads the kernel's grouped path
WIDTHS = {"gather": (64, 4, 2), "kernel": (1024, 16, 8)}


def tiny_cfg(width="gather", **over):
    d, h, kv = WIDTHS[width]
    kw = dict(vocab_size=V, n_layer=len(TYPES), n_head=h, n_kv_head=kv, d_model=d, d_ff=F, d_ff_dense=FD,
              max_seq_len=128, dtype="float32", tie_embeddings=True, norm="rmsnorm", position="rope",
              rope_theta=1e6, qk_norm="head", bias=False, mlp="moe", n_experts=E, experts_per_token=K,
              layer_ops=tuple("attn" if t == "full_attention" else t for t in TYPES),
              layer_mlps=("swiglu",) + ("moe",) * (len(TYPES) - 1), router_score="sigmoid",
              router_bias=True, norm_topk=True)
    kw.update(over)
    return serving.GPTConfig(**kw)


def tiny_model(cfg=None, params=None, **kw):
    cfg = cfg or tiny_cfg()
    kw = dict(dict(max_batch=4, n_blocks=64, block_size=16, prefill_buckets=[32, 64]), **kw)
    return serving.DecodeModel(cfg, params=params, seed=3, **kw)


def ref_logits(dm, seq):
    cfg = dm.cfg
    seq = np.asarray(seq, np.int32)[None]
    logits, routing = reference.logits_at(
        lambda n: dm.params[n], jnp.asarray(seq), jnp.asarray(np.arange(seq.shape[1])[None]),
        layer_types=TYPES, num_dense_layers=1, n_head=cfg.n_head, n_kv_head=cfg.kv_heads, top_k=K)
    return np.asarray(logits)[0], np.asarray(routing)[0]


def served_gap(dm, prompt, tokens):
    """How far below the reference's best logit each served token lies."""
    rows = ref_logits(dm, list(prompt) + list(tokens))[0][len(prompt) - 1:-1]
    return (rows.max(-1) - rows[np.arange(len(tokens)), tokens]).max()


@pytest.fixture(scope="module", params=sorted(WIDTHS))
def model(request):
    return tiny_model(tiny_cfg(request.param))


@pytest.fixture(scope="module")
def prompt():
    return np.random.RandomState(0).randint(0, V, 32).tolist()


def generate(eng, prompt, n):
    h = eng.submit(list(prompt), max_new_tokens=n)
    eng.run_until_idle()
    return h.result(timeout=5)


def test_full_logits_match_the_reference_and_route_alike(model, prompt):
    got, routing = model.full_logits(prompt[:24], with_routing=True)
    want, ref_routing = ref_logits(model, prompt[:24])
    assert np.abs(got[0] - want).max() <= TOL
    assert (np.sort(routing, -1) == np.sort(ref_routing, -1)).all()
    assert routing.shape == (24, len(TYPES) - 1, K)  # the expert layers only


def test_the_pools_belong_to_the_layers_that_use_them(model):
    cfg = model.cfg
    assert model.attention_path()[0] == ("kernel" if cfg.d_model == 1024 else "gather")
    # one attention layer of three K|V-head rows; three conv layers of two gated inputs a slot
    assert model.pool_shape() == (1 * 64, 16, cfg.kv_heads * 2 * cfg.head_dim)
    assert model.state_shape() == (3, 2, 4, cfg.d_model)
    assert model.kinds == [("conv", "swiglu"), ("attn", "moe"), ("conv", "moe")]


@pytest.mark.parametrize("n", [1, 2, 21, 32], ids=["one_token", "shorter_than_the_state", "mid_block",
                                                  "fills_a_bucket"])
def test_prefill_then_decode_through_both_pools_follows_the_references_full_forward(model, prompt, n):
    """Tokens served by the engine (prefill, then decode through the KV
    pool and the state pool), against the reference's teacher-forced
    forward over prompt + answer, at every position; a prompt shorter than
    the state leaves zeros in front of it."""
    ledger.reset()
    eng = serving.ServingEngine(model)
    tokens = generate(eng, prompt[:n], 20)
    doc = ledger.totals()
    ledger.reset()
    assert len(tokens) == 20 and served_gap(model, prompt[:n], tokens) <= TOL
    assert doc["state_writes"] == 1 and doc["state_pool_bytes"] == eng.state.nbytes
    assert doc["attn_layers"] == 1 and doc["moe_assignments"] == doc["decode_tokens"] * 3 * K


def test_a_slot_another_request_just_left_answers_as_it_does_alone(model, prompt):
    """One slot, three tenants in turn: the state the last one left (and
    what idle ticks wrote) is replaced whole by the next prefill."""
    dm = tiny_model(model.cfg, dict(model.params), max_batch=1)
    rng = np.random.RandomState(5)
    prompts = [prompt[:24], rng.randint(0, V, 2).tolist(), rng.randint(0, V, 1).tolist()]
    alone = [generate(serving.ServingEngine(dm), p, 10) for p in prompts]
    eng = serving.ServingEngine(dm)
    handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run_until_idle()
    assert [h.result(timeout=5) for h in handles] == alone
    assert all(served_gap(dm, p, t) <= TOL for p, t in zip(prompts, alone))


def test_an_admission_while_a_tick_is_in_flight_reads_the_right_state(model, prompt):
    """The tick in flight reads and writes every slot's state, the
    newcomer's slot too. The prefill is enqueued behind it unread (PR 48),
    and the pools' data dependence puts its write of the slot after that
    tick's pass and before the next tick's, which also takes the slot's
    first token from the device."""
    rng = np.random.RandomState(7)
    late = rng.randint(0, V, 19).tolist()
    ledger.reset()
    eng = serving.ServingEngine(model)
    first = eng.submit(prompt[:24], max_new_tokens=16)
    for _ in range(4):
        eng.step()
    before = eng._inflight
    assert before is not None  # a tick is out, unread
    second = eng.submit(late, max_new_tokens=16)
    eng.step()
    doc = ledger.totals()
    assert (doc["prefills"], doc["prefills_ahead"], doc["state_writes"]) == (2, 1, 2)
    assert not any(doc["pipeline_drains"].values())
    req = second._req
    assert eng._inflight is not before and req.unread == 1 and len(req.out_tokens) == 1
    # tick | prefill | tick: the windows follow the device's order
    assert first._req.tick_windows[-1][1] <= req.t_prefill0 < req.t_prefill1 <= eng._inflight.t0
    assert req.t_first_token == req.t_prefill1
    eng.run_until_idle()
    ledger.reset()
    assert served_gap(model, prompt[:24], first.result(timeout=5)) <= TOL
    assert served_gap(model, late, second.result(timeout=5)) <= TOL
    assert second.result() == generate(serving.ServingEngine(model), late, 16)
    for h in (first, second):
        assert sum(h.attribution.values()) == pytest.approx(h.engine_e2e_s, rel=1e-3, abs=1e-6)


def test_preempt_and_resume_give_the_uninterrupted_answer(model, prompt):
    """Recompute-on-resume rebuilds the conv state with the K and V: the
    resumed prefill runs over prompt + generated prefix."""
    want = generate(serving.ServingEngine(model), prompt[:20], 10)
    ledger.reset()
    eng = serving.ServingEngine(model)
    h = eng.submit(prompt[:20], max_new_tokens=10)
    for _ in range(4):
        eng.step()
    eng._drain("evict")
    req = h._req
    assert 0 < len(req.out_tokens) < 10
    eng._preempt(req)
    eng.run_until_idle()
    doc = ledger.totals()
    ledger.reset()
    assert h.result(timeout=5) == want and req.evictions == 1
    assert doc["state_writes"] == 2  # the admission's prefill and the resume's


def test_conv_bias_served_as_the_models_own_forward_has_it(prompt):
    """The biases the description allows on a conv layer's projections and
    taps (the published configuration has none): prefill and decode agree
    with the model's own non-paged forward."""
    cfg = tiny_cfg(conv_bias=True)
    params = serving.init_params(cfg, seed=3)
    rng = np.random.RandomState(2)
    for name in params:
        if ".conv." in name and name.endswith(".b"):
            params[name] = (0.3 * rng.randn(*params[name].shape)).astype(np.float32)
    dm = tiny_model(cfg, params)
    tokens = generate(serving.ServingEngine(dm), prompt[:9], 8)
    logits = dm.full_logits(prompt[:9] + tokens)[0][8:-1]
    assert (logits.max(-1) - logits[np.arange(8), tokens]).max() <= TOL
    plain = tiny_model(cfg, {k: v for k, v in params.items() if not k.endswith(".b")} |
                       {k: np.zeros_like(v) for k, v in params.items() if k.endswith(".b")})
    assert np.abs(plain.full_logits(prompt[:9]) - dm.full_logits(prompt[:9])).max() > 1e-3


def test_route_sigmoid_selects_under_the_bias_and_weighs_without_it():
    from paddle_tpu.ops import moe

    x = jnp.eye(2, dtype=jnp.float32)
    w = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.5, 0.4, 0.3, 0.2]], jnp.float32)
    s = 1.0 / (1.0 + np.exp(-np.asarray(w)))
    dense, idx = moe.route(x, w, 2, score="sigmoid", norm_topk=True)
    assert np.sort(np.asarray(idx), -1).tolist() == [[0, 1], [0, 1]]
    # a bias that lifts expert 3 over expert 1 flips token 0's selection ...
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.6], jnp.float32)
    dense, idx = moe.route(x, w, 2, score="sigmoid", bias=bias, norm_topk=True)
    assert np.sort(np.asarray(idx), -1).tolist() == [[0, 3], [0, 3]]
    # ... and the chosen weights are the UNBIASED scores over their sum
    for n in range(2):
        chosen = s[n, [0, 3]]
        np.testing.assert_allclose(np.asarray(dense)[n, [0, 3]], chosen / (chosen.sum() + 1e-6), rtol=1e-6)
        assert np.asarray(dense)[n].sum() == pytest.approx(1 / (1 + 1e-6 / chosen.sum()), rel=1e-6)
        assert (np.asarray(dense)[n, [1, 2]] == 0).all()
    scaled, _ = moe.route(x, w, 2, score="sigmoid", bias=bias, norm_topk=True, scale=2.5)
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(dense), rtol=1e-6)
    raw, _ = moe.route(x, w, 2, score="sigmoid", bias=bias)
    np.testing.assert_allclose(np.asarray(raw)[0, [0, 3]], s[0, [0, 3]], rtol=1e-6)
    with pytest.raises(ValueError, match="router score"):
        moe.route(x, w, 2, score="tanh")


def test_bfloat16_stays_within_the_runners_tolerance(prompt):
    tol = arch_modules.of({"model_type": "lfm2_moe"}).LOGIT_TOL
    dm = tiny_model(tiny_cfg(dtype="bfloat16"))
    tokens = generate(serving.ServingEngine(dm), prompt[:24], 16)
    assert served_gap(dm, prompt[:24], tokens) <= tol


def test_a_recipe_of_more_than_one_device_is_refused_by_name():
    with pytest.raises(NotImplementedError, match=r"recipe 'tp'.*experts.*`ep`"):
        tiny_model(recipe="tp")
    dense = tiny_cfg(mlp="swiglu", layer_mlps=None, n_experts=0, experts_per_token=0,
                     router_bias=False)
    with pytest.raises(NotImplementedError, match=r"recipe 'tp'.*conv layers.*state pool"):
        tiny_model(dense, recipe="tp")


def test_the_benchmarks_weight_table_names_what_the_program_reads():
    conf = {"n_layer": 4, "n_head": 4, "n_embd": 64, "num_key_value_heads": 2, "intermediate_size": FD,
            "moe_intermediate_size": F, "num_experts": E, "num_experts_per_tok": K, "vocab_size": V,
            "n_positions": 128, "norm_eps": 1e-5, "rope_parameters": {"rope_theta": 1e6, "rope_type": "default"},
            "layer_types": list(TYPES), "num_dense_layers": 1, "conv_L_cache": 3, "conv_bias": False,
            "norm_topk_prob": True, "use_expert_bias": True, "routed_scaling_factor": 1,
            "model_type": "lfm2_moe"}
    mod = arch_modules.of(conf)
    cfg = serving.GPTConfig(**mod.gpt_config(conf, {"dtype": "float32"}))
    assert cfg == tiny_cfg()
    mine, theirs = mod.param_table(conf), param_table(cfg)
    assert {k: v[0] for k, v in mine.items()} == {k: v[0] for k, v in theirs.items()}
    assert mod.n_params(conf) == sum(int(np.prod(s)) for s, _ in theirs.values())
    # seed-made weights: the same seed the same arrays, the names and shapes of the table
    a, b = mod.make_params(conf, 2**31 + 5, "float32"), mod.make_params(conf, 2**31 + 5, "float32")
    assert {k: v.shape for k, v in a.items()} == {k: v[0] for k, v in mine.items()}
    assert all((np.asarray(a[k]) == np.asarray(b[k])).all() for k in a)
    # gains around 1, the FIRST attention layer's q and k gains around QK_GAIN_FIRST
    assert float(jnp.mean(a["gpt.h1.attn.q_norm.scale"])) == pytest.approx(mod.QK_GAIN_FIRST, abs=0.4)
    assert float(jnp.mean(a["gpt.h0.ln1.scale"])) == pytest.approx(1.0, abs=0.2)
    assert float(jnp.std(a["gpt.h2.conv.taps.w"])) == pytest.approx(mod.TAPS_STD, rel=0.3)
    assert float(jnp.std(a["gpt.h2.moe.router.bias"])) == pytest.approx(mod.BIAS_STD, rel=0.6)
    with pytest.raises(SystemExit, match="layer_types names 4 layers"):
        mod.param_table(dict(conf, n_layer=3))


def test_the_published_configuration_maps_onto_the_block():
    from benchmark import manifest

    cell = manifest.cell(manifest.load(), "lfm2-serve-reason")
    c, e = cell["config"], cell["traffic"]["engine"]
    mod = arch_modules.of(c)
    cfg = serving.GPTConfig(**mod.gpt_config(c, e))
    assert (cfg.d_model, cfg.n_head, cfg.kv_heads, cfg.head_dim) == (2048, 32, 8, 64)
    assert (cfg.mlp_width("moe"), cfg.mlp_width("swiglu")) == (1536, 11776)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.router_score, cfg.router_bias, cfg.norm_topk,
            cfg.routed_scale) == (64, 4, "sigmoid", True, True, 1.0)
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.n_layer, cfg.tie_embeddings) == (65536, 3072, 10, True)
    assert (cfg.qk_norm, cfg.rope_theta, cfg.conv_kernel, cfg.conv_bias) == ("head", 1e6, 3, False)
    assert [cfg.layer_kind(i) for i in range(10)] == (
        [("conv", "swiglu")] * 2 + [("attn", "moe")] + [("conv", "moe")] * 3 + [("attn", "moe")]
        + [("conv", "moe")] * 3)
    assert (c["n_attn_layers"], c["n_conv_layers"], c["n_expert_layers"]) == (2, 8, 8)
    assert c["layer_types"] == c["published"]["layer_types"][:10] and len(c["published"]["layer_types"]) == 40
    assert mod.n_params(c) == 5_267_090_176 == c["assumed"]["parameters"]
    # the pools: the KV pool is the two attention layers' alone, 8 K|V heads
    # a row; the state pool a second array, two gated inputs a conv layer and slot
    import tools.serve_compile_report as report

    dm = report.abstract_model(cfg, **arch_modules.engine_args(e))
    assert dm.pool_shape() == (2 * e["n_blocks"], 16, 1024)
    assert dm.state_shape() == (8, 2, 64, 2048) and dm.attention_path() == ("kernel", "")
    assert mod.kv_token_bytes(c) == 4096 and mod.state_bytes(c, 64) == 8 * 2 * 64 * 2048 * 2
    # every slot at the longest request keeps its blocks (less the scratch block 0)
    longest = cell["traffic"]["prompt_len"]["hi"] + cell["traffic"]["output_len"]["hi"]
    assert e["max_batch"] * -(-(longest + 1) // e["block_size"]) <= e["n_blocks"]
    assert longest + 1 <= cell["traffic"]["max_total"] <= cfg.max_seq_len


def test_decode_tick_bytes_count_what_a_tick_must_move():
    from benchmark import manifest

    c = manifest.cell(manifest.load(), "lfm2-serve-reason")["config"]
    mod = arch_modules.of(c)
    parts = mod.decode_tick_bytes(c, 64, 90_000.0, 8 * 64)
    assert parts["experts"] == 8 * 64 * 3 * 2048 * 1536 * 2 == 8 * 64 * mod.expert_bytes(c)
    assert parts["kv"] == 90_000 * 4096 == mod.paged_attention_bytes(c, 90_000.0)
    assert parts["state"] == 2 * 8 * 2 * 64 * 2048 * 2
    # every other weight once: the whole model less the experts
    assert parts["experts"] + parts["other_weights"] == 2 * mod.n_params(c)
    assert mod.expert_shapes(c) == ["[64,2048,1536]", "[64,1536,2048]"]
    assert mod.conv_shapes(c) == {"[2048,6144]": 1.0, "[3,2048]": 1.0, "[2048,2048]": 8 / 12}
