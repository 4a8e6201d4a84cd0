"""The OLMoE block (RMSNorm, RoPE, q/k norm, drop-free top-k SwiGLU experts,
untied head) served by the ONE DecodeModel, against the plain float32
reference in benchmark/reference/olmoe.py: tiny widths, CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import arch as arch_modules
from benchmark.reference import olmoe as reference
from paddle_tpu import serving
from paddle_tpu.serving import ledger
from paddle_tpu.serving.model import param_table

L, H, D, F, E, K, V = 2, 4, 64, 32, 8, 2, 256
TOL = 1e-4  # float32 against float32: rounding and summation order only


def tiny_cfg(**over):
    kw = dict(vocab_size=V, n_layer=L, n_head=H, d_model=D, d_ff=F, max_seq_len=128, dtype="float32",
              tie_embeddings=False, norm="rmsnorm", position="rope", qk_norm=True, bias=False,
              mlp="moe", n_experts=E, experts_per_token=K)
    kw.update(over)
    return serving.GPTConfig(**kw)


def tiny_model(cfg=None, params=None, **kw):
    cfg = cfg or tiny_cfg()
    kw = dict(dict(max_batch=4, n_blocks=64, block_size=16, prefill_buckets=[32, 64]), **kw)
    return serving.DecodeModel(cfg, params=params, seed=3, **kw)


def ref_logits(params, seq, positions=None, **kw):
    seq = np.asarray(seq, np.int32)[None]
    pos = np.arange(seq.shape[1])[None] if positions is None else np.asarray(positions)[None]
    kw = dict(dict(n_layer=L, n_head=H, top_k=K), **kw)
    logits, routing = reference.logits_at(lambda n: params[n], jnp.asarray(seq), jnp.asarray(pos), **kw)
    return np.asarray(logits)[0], np.asarray(routing)[0]


@pytest.fixture(scope="module")
def model():
    return tiny_model()


@pytest.fixture(scope="module")
def prompt():
    return np.random.RandomState(0).randint(0, V, 24).tolist()


def serve(dm, requests, max_new=12):
    """Requests through Router -> ServingEngine -> DecodeModel, all in
    flight together; their token lists, in order."""
    import threading

    eng = serving.ServingEngine(dm)
    eng.start()
    router = serving.Router([serving.LocalReplica("r0", eng)])
    out = [None] * len(requests)

    def one(i):
        out[i] = router.dispatch(list(requests[i]), max_new_tokens=max_new, deadline_s=120,
                                 request_id=f"q{i}")
    try:
        ts = [threading.Thread(target=one, args=(i,)) for i in range(len(requests))]
        [t.start() for t in ts]
        [t.join() for t in ts]
    finally:
        router.stop()
        eng.stop()
    assert all(r["ok"] for r in out), [r.get("error") for r in out]
    return [r["tokens"] for r in out]


def serve_staggered(dm, requests, max_new=12):
    """The same requests through one engine driven by hand, each after the
    first admitted while a decode tick is in flight, unread."""
    eng = serving.ServingEngine(dm)
    hs = [eng.submit(list(requests[0]), max_new_tokens=max_new)]
    for _ in range(3):
        eng.step()
    for r in requests[1:]:
        assert eng._inflight is not None
        hs.append(eng.submit(list(r), max_new_tokens=max_new))
        eng.step()
        assert hs[-1]._req.unread == 1 and len(hs[-1]._req.out_tokens) == 1
    eng.run_until_idle()
    for h in hs:
        assert sum(h.attribution.values()) == pytest.approx(h.engine_e2e_s, rel=1e-3, abs=1e-6)
        req = h._req
        spans = [(req.t_prefill0, req.t_prefill1)] + [(t0, t1) for t0, t1, _ in req.tick_windows]
        assert all(a1 <= b0 < b1 for (_, a1), (b0, b1) in zip(spans, spans[1:])), spans
        assert req.t_first_token == req.t_prefill1
    return [h.result(timeout=5) for h in hs]


def test_full_logits_match_the_reference_and_route_alike(model, prompt):
    got, routing = model.full_logits(prompt, with_routing=True)
    want, ref_routing = ref_logits(model.params, prompt)
    assert np.abs(got[0] - want).max() <= TOL
    assert (np.sort(routing, -1) == np.sort(ref_routing, -1)).all()
    assert routing.shape == (len(prompt), L, K)


def test_score_matches_the_reference(model, prompt):
    nll, total = model.score(prompt)
    logits, _ = ref_logits(model.params, prompt)
    logp = jax.nn.log_softmax(jnp.asarray(logits[:-1]), axis=-1)
    want = -np.asarray(logp)[np.arange(len(prompt) - 1), prompt[1:]]
    np.testing.assert_allclose(nll, want, atol=TOL, rtol=TOL)
    assert abs(total - want.sum()) <= 1e-3


@pytest.mark.parametrize("others,max_new,how", [((), 16, serve), ((9, 30, 17), 36, serve),
                                                ((9, 30, 17), 36, serve_staggered)],
                         ids=["alone", "four_long", "four_long_staggered"])
def test_prefill_then_decode_through_the_cache_follows_the_references_full_forward(model, prompt, others, max_new,
                                                                                   how):
    """Tokens served by the engine, against the reference's teacher-forced
    forward over prompt + answer: at every position the served token is
    the reference's argmax (its logit within TOL of the best). Every tick
    but the first of a run is enqueued on the last one's unread tokens,
    routing counts behind them; an admission's prefill writes its token
    among them on the device and leaves the counts as they were
    (``staggered``: each admission finds a tick in flight)."""
    rng = np.random.RandomState(5)
    prompts = [prompt] + [rng.randint(0, V, n).tolist() for n in others]
    ledger.reset()
    answers = how(model, prompts, max_new=max_new)
    doc = ledger.totals()
    ledger.reset()
    for p, tokens in zip(prompts, answers):
        seq = p + tokens
        logits, _ = ref_logits(model.params, seq)
        rows = logits[len(p) - 1:len(seq) - 1]
        gaps = rows.max(-1) - rows[np.arange(len(tokens)), tokens]
        assert len(tokens) == max_new and gaps.max() <= TOL, gaps
    # no admission reads the tick in flight first: every tick goes out
    # ahead but the first after the engine had run dry
    drains = doc["pipeline_drains"]
    assert drains["empty"] >= 1 and not (drains["evict"] or drains["error"])
    assert doc["ticks_ahead"] == doc["decode_ticks"] - drains["empty"]
    assert doc["prefills"] == len(prompts) and doc["prefills_ahead"] <= len(others)
    if how is serve_staggered:
        assert doc["prefills_ahead"] == len(others) and drains["empty"] == 1
    if others:
        assert doc["ticks_ahead"] / doc["decode_ticks"] > 0.8, doc["pipeline_drains"]
    assert doc["moe_assignments"] == doc["decode_tokens"] * L * K


def test_a_request_alone_and_in_a_full_batch_bit_for_bit(model, prompt):
    rng = np.random.RandomState(5)
    others = [rng.randint(0, V, n).tolist() for n in (9, 30, 17)]
    (alone,) = serve(model, [prompt])
    batched = serve(model, [prompt] + others)
    assert batched[0] == alone
    # and the decode program itself: the same row, alone or among others
    pages = model.init_pages()
    B, nb = model.max_batch, model.max_blocks_per_req
    pages, _, _, _ = model.prefill_enqueue(pages, None, np.asarray(prompt), len(prompt), [1, 2])
    tables = np.zeros((B, nb), np.int32)
    tables[0, :2] = [1, 2]
    lens, toks = np.zeros(B, np.int32), np.zeros(B, np.int32)
    lens[0], toks[0] = len(prompt), 7
    full_t, full_l, full_k = tables.copy(), lens.copy(), toks.copy()
    for s, n in ((1, 9), (2, 30), (3, 17)):
        pages, _, _, _ = model.prefill_enqueue(pages, None, np.asarray(others[s - 1]), n, [1 + 2 * s, 2 + 2 * s],
                                               slot=s)
        full_t[s, :2] = [1 + 2 * s, 2 + 2 * s]
        full_l[s], full_k[s] = n, 11
    pages, _, nxt, _ = model.decode_enqueue(pages, None, tables, lens, toks)
    a, lone_routing = model.decode_read(nxt)
    pages, _, nxt, _ = model.decode_enqueue(pages, None, full_t, full_l, full_k)
    b, full_routing = model.decode_read(nxt)
    assert a[0] == b[0]
    assert lone_routing[0] == L * K and full_routing[0] == 4 * L * K


def test_every_token_to_one_expert_nothing_dropped_still_exact(prompt):
    """A crafted router, all zeros: the softmax is flat and top-k takes
    the lowest ids, so EVERY token of every layer goes to experts 0 and 1
    and six experts see nothing. A capacity factor would drop tokens
    here; this layer has none."""
    cfg = tiny_cfg()
    params = serving.init_params(cfg, seed=3)
    for i in range(L):
        params[f"gpt.h{i}.moe.router.w"] = np.zeros((D, E), np.float32)
    dm = tiny_model(cfg, params)
    got, routing = dm.full_logits(prompt, with_routing=True)
    assert (np.sort(routing, -1) == np.array([0, 1])).all()
    want, ref_routing = ref_logits(dm.params, prompt)
    assert (np.sort(ref_routing, -1) == np.array([0, 1])).all()
    assert np.abs(got[0] - want).max() <= TOL
    ledger.reset()  # and in the engine
    (tokens,) = serve(dm, [prompt], max_new=8)
    logits, _ = ref_logits(dm.params, prompt + tokens)
    rows = logits[len(prompt) - 1:-1]
    assert (rows.max(-1) - rows[np.arange(8), tokens]).max() <= TOL
    t = ledger.totals()
    assert t["moe_experts_hit"] == 2 * L * t["decode_ticks"]
    assert t["moe_max_load"] == L * t["decode_ticks"]  # one slot: each expert holds it once


def test_bfloat16_stays_within_the_runners_tolerance(prompt):
    tol = arch_modules.of({"model_type": "olmoe"}).LOGIT_TOL
    cfg = tiny_cfg(dtype="bfloat16")
    dm = tiny_model(cfg)
    (tokens,) = serve(dm, [prompt], max_new=16)
    logits, _ = ref_logits(dm.params, prompt + tokens)
    rows = logits[len(prompt) - 1:-1]
    assert (rows.max(-1) - rows[np.arange(16), tokens]).max() <= tol


def test_a_recipe_of_more_than_one_device_with_experts_is_refused_by_name():
    with pytest.raises(NotImplementedError, match=r"recipe 'tp'.*experts.*`ep`"):
        tiny_model(recipe="tp")


def test_the_training_graph_refuses_a_block_it_does_not_build():
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt

    paddle.enable_static()
    try:
        with pytest.raises(NotImplementedError, match="GPT-2 block only"):
            gpt.build_train_program(tiny_cfg(), batch=2, seq=16)
    finally:
        paddle.disable_static()


def test_ledger_routing_counters_against_a_hand_count():
    led = ledger.ServingLedger()
    # two ticks: (16 pairs, 7 experts, busiest 4) and (8, 8, 1)
    led.note_routing(16, 7, 4)
    led.note_routing(np.int32(8), np.int32(8), np.int32(1))
    doc = led.totals()
    assert (doc["moe_assignments"], doc["moe_experts_hit"], doc["moe_max_load"]) == (24, 15, 5)
    merged = ledger.merge_ledgers([doc, doc])
    assert merged["moe_experts_hit"] == 30 and merged["moe_assignments"] == 48
    ledger.reset()
    ledger.note_routing(3, 2, 1)
    assert ledger.totals()["moe_assignments"] == 3
    ledger.reset()
    assert ledger.totals()["moe_assignments"] == 0


def test_routing_counts_of_the_op_against_numpy():
    from paddle_tpu.ops import moe

    idx = np.array([[0, 3], [3, 5], [3, 0], [7, 6]], np.int32)
    live = np.array([True, True, False, True])
    got = np.asarray(moe.routing_counts(jnp.asarray(idx), jnp.asarray(live), 8))
    # live rows: experts 0,3 | 3,5 | 7,6 -> 6 pairs, experts {0,3,5,6,7}, expert 3 twice
    assert got.tolist() == [6, 5, 2]


def test_the_benchmarks_weight_table_names_what_the_program_reads():
    conf = {"n_layer": L, "n_head": H, "n_embd": D, "intermediate_size": F, "num_experts": E,
            "num_experts_per_tok": K, "vocab_size": V, "n_positions": 128, "rms_norm_eps": 1e-5,
            "rope_theta": 10000, "tie_word_embeddings": False, "attention_bias": False,
            "norm_topk_prob": False, "rope_scaling": None, "clip_qkv": None, "model_type": "olmoe"}
    mod = arch_modules.of(conf)
    cfg = serving.GPTConfig(**mod.gpt_config(conf, {"dtype": "float32"}))
    assert cfg == tiny_cfg(max_seq_len=128)
    mine, theirs = mod.param_table(conf), param_table(cfg)
    assert {k: v[0] for k, v in mine.items()} == {k: v[0] for k, v in theirs.items()}
    assert mod.n_params(conf) == sum(int(np.prod(s)) for s, _ in theirs.values())
    # seed-made weights: the same seed the same arrays, the names and shapes of the table
    a, b = mod.make_params(conf, 2**31 + 5, "float32"), mod.make_params(conf, 2**31 + 5, "float32")
    assert {k: v.shape for k, v in a.items()} == {k: v[0] for k, v in mine.items()}
    assert all((np.asarray(a[k]) == np.asarray(b[k])).all() for k in a)
    assert float(jnp.std(a["gpt.h1.moe.gate.w"])) == pytest.approx(0.02, rel=0.05)
    # the gains are drawn too: around 1, the first layer's q and k gains around QK_GAIN_FIRST
    assert float(jnp.mean(a["gpt.h0.ln1.scale"])) == pytest.approx(1.0, abs=0.15)
    assert float(jnp.mean(a["gpt.h0.attn.q_norm.scale"])) == pytest.approx(mod.QK_GAIN_FIRST, abs=0.3)
    assert float(jnp.mean(a["gpt.h1.attn.k_norm.scale"])) == pytest.approx(1.0, abs=0.15)
    assert float(jnp.std(a["gpt.h1.attn.k_norm.scale"])) > 0.15


def test_the_published_configuration_maps_onto_the_block():
    from benchmark import manifest

    cell = manifest.cell(manifest.load(), "olmoe-serve-batch")
    c, e = cell["config"], cell["traffic"]["engine"]
    cfg = serving.GPTConfig(**arch_modules.of(c).gpt_config(c, e))
    assert (cfg.d_model, cfg.n_head, cfg.head_dim, cfg.ffn_dim) == (2048, 16, 128, 1024)
    assert (cfg.n_experts, cfg.experts_per_token) == (64, 8)
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.n_layer, cfg.tie_embeddings) == (50304, 1024, 12, False)
    assert arch_modules.of(c).n_params(c) == 5_240_883_200 == c["assumed"]["parameters"]
    # every slot at the longest request has its blocks (less the scratch
    # block 0, should all 24 ever be at their last token together)
    longest = cell["traffic"]["prompt_len"]["hi"] + cell["traffic"]["output_len"]["hi"]
    assert e["max_batch"] * -(-longest // e["block_size"]) <= e["n_blocks"]
