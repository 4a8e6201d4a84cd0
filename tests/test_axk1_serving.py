"""The A.X-K1 block (latent attention over a latent paged pool in two forms,
YaRN, a dense layer then routed experts under group-limited sigmoid routing
beside a shared expert, one chip's share of the router's experts, untied
head) served by the ONE DecodeModel, against the plain float32 reference in
benchmark/reference/axk1.py: tiny widths that keep the structure, CPU."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import arch as arch_modules
from benchmark.reference import axk1 as reference
from paddle_tpu import serving
from paddle_tpu.models.gpt import YarnRope
from paddle_tpu.ops import moe
from paddle_tpu.serving import ledger
from paddle_tpu.serving.model import param_table

TOL = 1e-4  # float32 against float32: rounding and summation order only
V, E, K, GROUPS, KEEP, HELD = 256, 16, 4, 4, 2, (2, 2)
# a configuration file's keys at a size a CPU test carries: q and K|V ranks,
# rotated lanes that are no multiple of the latent, 16 experts in 4 groups
# of which 2 are kept, experts 2..3 held (2 of 16 for the cell's 12 of 192)
CONF = {"model_type": "axk1", "n_layer": 3, "n_head": 4, "n_embd": 64, "n_positions": 4096, "vocab_size": V,
        "attention_bias": False, "first_k_dense_replace": 1, "intermediate_size": 48, "kv_lora_rank": 128,
        "moe_intermediate_size": 32, "n_group": GROUPS, "n_routed_experts": HELD[1], "router_experts": E,
        "first_expert_held": HELD[0], "n_shared_experts": 1, "norm_topk_prob": True, "num_experts_per_tok": K,
        "q_lora_rank": 48, "qk_nope_head_dim": 16, "qk_rope_head_dim": 32, "rms_norm_eps": 1e-6,
        "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 16, "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": KEEP, "topk_method": "none", "v_head_dim": 24}
# (K|V latent, rotated lanes): a latent of one whole 128-lane tile takes the
# kernel's latent mode, a narrower one the gathered window
WIDTHS = {"kernel": (128, 32), "gather": (32, 16)}
MOD = arch_modules.of(CONF)


def conf_of(width="kernel", **over):
    kv, rope = WIDTHS[width]
    return dict(CONF, kv_lora_rank=kv, qk_rope_head_dim=rope, **over)


def tiny_model(width="kernel", dtype="float32", conf=None, params=None, **kw):
    conf = conf or conf_of(width)
    cfg = serving.GPTConfig(**MOD.gpt_config(conf, {"dtype": dtype, "window": 128}))
    params = params if params is not None else MOD.make_params(conf, 2**31 + 5, dtype)
    kw = dict(dict(max_batch=4, n_blocks=64, block_size=16, prefill_buckets=[32, 64]), **kw)
    dm = serving.DecodeModel(cfg, params=params, **kw)
    dm.conf = conf
    return dm


def ref_logits(dm, seq):
    seq = np.asarray(seq, np.int32)[None]
    logits, routing = MOD.reference_logits(lambda n: dm.params[n], jnp.asarray(seq),
                                           jnp.asarray(np.arange(seq.shape[1])[None]), dm.conf)
    return np.asarray(logits)[0], np.asarray(routing)[0]


def served_gap(dm, prompt, tokens):
    """How far below the reference's best logit each served token lies."""
    rows = ref_logits(dm, list(prompt) + list(tokens))[0][len(prompt) - 1:-1]
    return (rows.max(-1) - rows[np.arange(len(tokens)), tokens]).max()


@pytest.fixture(scope="module", params=sorted(WIDTHS))
def model(request):
    return tiny_model(request.param)


@pytest.fixture(scope="module")
def prompt():
    return np.random.RandomState(0).randint(0, V, 40).tolist()


def generate(eng, prompt, n):
    h = eng.submit(list(prompt), max_new_tokens=n)
    eng.run_until_idle()
    return h.result(timeout=5)


def test_the_expanded_form_matches_the_reference_and_routes_alike(model, prompt):
    got, routing = model.full_logits(prompt[:24], with_routing=True)
    want, ref_routing = ref_logits(model, prompt[:24])
    assert np.abs(got[0] - want).max() <= TOL
    assert (np.sort(routing, -1) == np.sort(ref_routing, -1)).all()
    assert routing.shape == (24, 2, K) and routing.max() >= E // 2  # ids over the router's 16, not the 2 held


def test_one_row_a_position_for_every_head(model):
    cfg = model.cfg
    kv, rope = cfg.kv_lora_rank, cfg.qk_rope_dim
    assert model.attention_path()[0] == ("kernel" if kv == 128 else "gather")
    assert "whole 128-lane tiles" in model.attention_path()[1] or kv == 128
    # every layer attends: three layers' blocks, rows of latent + rotated lanes padded to whole tiles
    assert model.pool_shape() == (3 * 64, 16, 256 if kv == 128 else 128) and cfg.latent_row == kv + rope
    assert model.state_shape() is None and model.attn_layers == [0, 1, 2]
    assert model.kinds == [("latent", "swiglu"), ("latent", "moe")]
    assert cfg.held_experts == HELD and model.params["gpt.h1.moe.gate.w"].shape == (2, 64, 32)
    assert model.params["gpt.h1.moe.router.w"].shape == (64, E)


@pytest.mark.parametrize("n", [1, 21, 32, 40], ids=["one_token", "mid_block", "fills_a_bucket", "second_bucket"])
def test_prefill_then_decode_through_the_latent_pool_follows_the_references_full_forward(model, prompt, n):
    """Tokens served by the engine (prefill in the expanded form writing the
    latent rows, then decode in the absorbed form over them), against the
    reference's teacher-forced forward over prompt + answer, at every
    position; and the absorbed form against the model's own expanded one."""
    ledger.reset()
    eng = serving.ServingEngine(model)
    tokens = generate(eng, prompt[:n], 20)
    doc = ledger.totals()
    ledger.reset()
    assert len(tokens) == 20 and served_gap(model, prompt[:n], tokens) <= TOL
    own = model.full_logits(prompt[:n] + tokens)[0][n - 1:-1]
    assert (own.max(-1) - own[np.arange(20), tokens]).max() <= TOL  # absorbed == expanded
    # the counters: two expert layers; held assignments among all the router's
    assert doc["attn_layers"] == 3 and doc["moe_assignments_routed"] == doc["decode_tokens"] * 2 * K
    assert 0 <= doc["moe_assignments"] <= doc["moe_assignments_routed"]
    assert doc["moe_experts_hit"] <= doc["decode_ticks"] * 2 * HELD[1]


def test_held_assignments_count_the_held_experts_alone(model, prompt):
    seq = prompt[:12]
    ledger.reset()
    tokens = generate(serving.ServingEngine(model), seq, 9)
    doc = ledger.totals()
    ledger.reset()
    # decode ticks route the tokens at positions 12 .. 19 (the first answer token is the prefill's)
    _, routing = model.full_logits(seq + tokens, with_routing=True)
    mine = routing[12:20]
    first, held = HELD
    assert doc["moe_assignments"] == int(((mine >= first) & (mine < first + held)).sum())
    assert doc["moe_assignments_routed"] == mine.size


@pytest.mark.parametrize("dtype", ["bfloat16"])
def test_bfloat16_stays_within_the_runners_tolerance(prompt, dtype):
    dm = tiny_model("kernel", dtype)
    tokens = generate(serving.ServingEngine(dm), prompt[:24], 16)
    assert served_gap(dm, prompt[:24], tokens) <= MOD.LOGIT_TOL


def test_batched_requests_answer_as_each_does_alone(model, prompt):
    rng = np.random.RandomState(5)
    prompts = [prompt[:24], rng.randint(0, V, 7).tolist(), rng.randint(0, V, 33).tolist()]
    alone = [generate(serving.ServingEngine(model), p, 10) for p in prompts]
    eng = serving.ServingEngine(model)
    handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run_until_idle()
    assert [h.result(timeout=5) for h in handles] == alone


def test_evict_and_resume_of_a_latent_slot_give_the_uninterrupted_answer(model, prompt):
    """Recompute-on-resume rebuilds the latent rows: the resumed prefill
    runs over prompt + generated prefix, in the expanded form."""
    want = generate(serving.ServingEngine(model), prompt[:20], 10)
    eng = serving.ServingEngine(model)
    h = eng.submit(prompt[:20], max_new_tokens=10)
    for _ in range(4):
        eng.step()
    eng._drain("evict")
    req = h._req
    assert 0 < len(req.out_tokens) < 10
    eng._preempt(req)
    eng.run_until_idle()
    assert h.result(timeout=5) == want and req.evictions == 1
    assert served_gap(model, prompt[:20], want) <= TOL


def test_yarn_frequencies_and_the_softmax_gain_by_hand():
    """The published row: 64 rotated lanes, base 10,000, factor 32 over
    4,096 original positions, beta 32 / 1, mscale = mscale_all_dim = 1."""
    yarn = YarnRope(32.0, 4096, 32.0, 1.0, 1.0, 1.0)
    # low = floor(64 ln(4096 / (32 * 2 pi)) / (2 ln 10000)) = floor(10.47), high = ceil(22.51)
    assert yarn.ramp_bounds(64, 10000.0) == (10, 23)
    inv = yarn.inv_freq(64, 10000.0)
    ref_inv, low, high = reference.yarn_inv_freq(64, 10000.0, 32, 4096, 32, 1)
    assert (low, high) == (10, 23) and np.allclose(inv, ref_inv, rtol=1e-12)
    f = lambda i: 10000.0 ** (-2 * i / 64)
    assert inv[0] == 1.0 and inv[10] == pytest.approx(f(10))          # kept: ramp 0 up to low
    assert inv[23] == pytest.approx(f(23) / 32) and inv[31] == pytest.approx(f(31) / 32)  # stretched from high on
    # between: i = 16, ramp 6 / 13; f_16 = 10000^-0.5 = 0.01
    assert inv[16] == pytest.approx(0.01 * (6 / 13 / 32 + 7 / 13)) == pytest.approx(0.0055288, rel=1e-4)
    # m = 0.1 ln 32 + 1 = 1.34657; the scale 192^-0.5 m^2; cos and sin carry 1
    assert yarn.softmax_gain() == pytest.approx(1.34657 ** 2, rel=1e-5) == pytest.approx(1.81326, rel=1e-5)
    assert yarn.attention_factor() == 1.0 and reference.yarn_mscale(32, 1) == pytest.approx(1.34657, rel=1e-5)
    assert YarnRope(32.0, 4096, mscale=0.707, mscale_all_dim=1.0).attention_factor() == pytest.approx(
        (0.0707 * math.log(32) + 1) / 1.34657, rel=1e-4)
    c = serving.GPTConfig(n_layer=1, n_head=64, d_model=7168, layer_ops=("latent",), position="rope",
                          q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
                          rope_yarn=yarn)
    dm = object.__new__(serving.DecodeModel)
    dm.cfg, dm.latent = c, True
    assert dm._latent_scale() == pytest.approx(192 ** -0.5 * 1.81326, rel=1e-5) and c.latent_row == 576
    # the stretched frequencies hold at EVERY position, not only past 4,096
    cos, sin = dm._rot(jnp.asarray([0, 7, 5000]))
    assert cos.shape == (3, 1, 32) and np.allclose(np.asarray(cos)[1, 0], np.cos(7 * inv), atol=1e-6)
    assert np.allclose(np.asarray(sin)[2, 0], np.sin(np.float32(5000) * inv.astype(np.float32)), atol=2e-3)


def test_rotated_lanes_pair_neighbours():
    from paddle_tpu.serving.model import _rope, _rope_pairs

    x = jnp.asarray(np.random.RandomState(1).randn(3, 2, 8), jnp.float32)
    ang = jnp.asarray(np.random.RandomState(2).rand(3, 1, 4), jnp.float32)
    rot = (jnp.cos(ang), jnp.sin(ang))
    got = np.asarray(_rope_pairs(x, rot))
    z = (np.asarray(x)[..., 0::2] + 1j * np.asarray(x)[..., 1::2]) * np.exp(1j * np.asarray(ang))
    assert np.allclose(got[..., 0::2], z.real, atol=1e-6) and np.allclose(got[..., 1::2], z.imag, atol=1e-6)
    # the other convention (lane i with lane i + 4) is another function of the same lanes
    assert np.abs(got - np.asarray(_rope(x, rot))).max() > 0.1
    ref = np.asarray(reference._rope(x[:, 0], [0.5, 0.25, 0.125, 0.0625], 1.0))
    ang = np.arange(3)[:, None] * np.asarray([0.5, 0.25, 0.125, 0.0625])[None]
    assert np.allclose(ref, np.asarray(_rope_pairs(x[:, 0], (jnp.cos(ang), jnp.sin(ang)))), atol=1e-6)


def _layer_weights(rng, d=16, f=8):
    gate, up = rng.randn(E, d, f).astype(np.float32) * 0.3, rng.randn(E, d, f).astype(np.float32) * 0.3
    down = rng.randn(E, f, d).astype(np.float32) * 0.3
    shared = [rng.randn(d, f).astype(np.float32) * 0.3, rng.randn(d, f).astype(np.float32) * 0.3,
              rng.randn(f, d).astype(np.float32) * 0.3]
    return rng.randn(d, E).astype(np.float32), gate, up, down, shared


@pytest.mark.parametrize("held", [1, 2, 4, 16])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """Over all the (first, held) shares of the 16 experts, the routed parts
    that ``moe.experts`` gives, with the shared expert counted ONCE, sum to
    what the uncut reference gives for the whole layer: nothing is lost or
    counted twice between chips."""
    rng = np.random.RandomState(3)
    router, gate, up, down, shared = _layer_weights(rng)
    x = jnp.asarray(rng.randn(24, 16), jnp.float32)
    w = {"moe.router.w": router, "moe.gate.w": gate, "moe.up.w": up, "moe.down.w": down,
         "moe.shared.gate.w": shared[0], "moe.shared.up.w": shared[1], "moe.shared.down.w": shared[2]}
    mm = reference._mm(None)
    whole, ref_idx = reference._experts(x, w, mm, K, GROUPS, KEEP, 2.5, 0)       # all 16 experts
    dense, idx = moe.route(x, jnp.asarray(router), K, score="sigmoid", norm_topk=True, scale=2.5,
                           norm_eps=1e-20, groups=GROUPS, keep_groups=KEEP)
    assert (np.sort(idx, -1) == np.sort(ref_idx, -1)).all()
    parts = [moe.experts(x, dense, gate[a:a + held], up[a:a + held], down[a:a + held], share=(a, held))
             for a in range(0, E, held)]
    once = reference._swiglu(x, *shared, mm)
    assert np.abs(np.asarray(sum(parts) + once) - np.asarray(whole)).max() <= 1e-5
    # and share by share the reference agrees with the program's part
    for a, part in zip(range(0, E, held), parts):
        cut = dict(w, **{k: w[k][a:a + held] for k in ("moe.gate.w", "moe.up.w", "moe.down.w")})
        ref_part, _ = reference._experts(x, cut, mm, K, GROUPS, KEEP, 2.5, a)
        assert np.abs(np.asarray(part + once) - np.asarray(ref_part)).max() <= 1e-5
    if held < E:  # a share is no whole: the test can fail
        assert np.abs(np.asarray(parts[0] + once) - np.asarray(whole)).max() > 1e-3


def test_group_limited_selection_stays_inside_the_kept_groups():
    rng = np.random.RandomState(7)
    x, w = jnp.asarray(rng.randn(200, 16), jnp.float32), jnp.asarray(rng.randn(16, E), jnp.float32)
    s = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(w))))
    dense, idx = moe.route(x, w, K, score="sigmoid", groups=GROUPS, keep_groups=KEEP)
    idx = np.asarray(idx)
    # a group's score: the sum of its two largest; the 2 best of the 4 groups of 4 are open
    top2 = np.sort(s.reshape(200, GROUPS, E // GROUPS), -1)[..., -2:].sum(-1)
    open_ = np.argsort(-top2, -1)[:, :KEEP]
    assert all(set(idx[n] // (E // GROUPS)) <= set(open_[n]) for n in range(200))
    # ... and inside them the token takes its K largest scores, weighted by those scores
    for n in range(200):
        allowed = np.flatnonzero(np.isin(np.arange(E) // (E // GROUPS), open_[n]))
        assert set(idx[n]) == set(allowed[np.argsort(-s[n, allowed])[:K]])
        assert np.allclose(np.asarray(dense)[n, idx[n]], s[n, idx[n]], rtol=1e-6)
    plain_dense, plain = moe.route(x, w, K, score="sigmoid")
    assert (np.sort(np.asarray(plain), -1) != np.sort(idx, -1)).any()  # the limit binds somewhere
    # every group kept: plain top-k
    all_dense, every = moe.route(x, w, K, score="sigmoid", groups=GROUPS, keep_groups=GROUPS)
    assert (np.asarray(every) == np.asarray(plain)).all() and np.allclose(all_dense, plain_dense)
    # the norm's epsilon is the description's
    a, _ = moe.route(x, w, K, score="sigmoid", norm_topk=True, norm_eps=0.5)
    b = np.asarray(plain_dense)
    assert np.allclose(a, b / (b.sum(-1, keepdims=True) + 0.5), rtol=1e-6)


def test_routing_counts_of_a_share_count_the_held_experts():
    idx = jnp.asarray([[0, 5], [4, 5], [5, 9], [4, 3]])
    live = jnp.asarray([True, True, False, True])
    assert moe.routing_counts(idx, live, 12).tolist() == [6, 4, 2]          # experts 0, 3, 4, 5; 5 and 4 twice
    # experts 4..7 held: (4, 5) of token 1, 5 of token 0, 4 of token 3; 6 assignments routed over all 12
    assert moe.routing_counts(idx, live, 12, share=(4, 4)).tolist() == [4, 2, 2, 6]


# the latent mode alone: 4 heads over one shared row of 160 used lanes in 256,
# a window of 72 pages (two of the longest steps and a page more)
_KERNEL_CASE = dict(H=4, r=160, hw=256, v=128, bs=16, nb=6 * 72 + 1, maxb=72)


def _latent_step(dtype):
    """Positions a step of the kernel holds at the case's row, by the
    kernel's own rule."""
    from paddle_tpu.ops.pallas.paged_attention import step_schedule

    return step_schedule(_KERNEL_CASE["hw"] * jnp.dtype(dtype).itemsize).positions


def _latent_kernel_case(lens, dtype, loud=()):
    """The kernel's output and the gathered formulation's on one pool:
    every slot its own scattered pages, ``lens`` the new tokens' positions;
    the pages of the slots in ``loud`` hold rows of 1e4."""
    from paddle_tpu.ops.pallas.paged_attention import paged_latent_attention

    H, r, hw, v, bs, nb, maxb = (_KERNEL_CASE[k] for k in ("H", "r", "hw", "v", "bs", "nb", "maxb"))
    rng = np.random.RandomState(0)
    B = len(lens)
    pool = np.zeros((nb, bs, hw), np.float32)
    pool[:, :, :r] = rng.randn(nb, bs, r)
    q = rng.randn(B, H, r).astype(np.float32)
    tables = rng.permutation(np.arange(1, nb))[:B * maxb].reshape(B, maxb).astype(np.int32)
    for b in loud:
        pool[tables[b], :, :r] = 1e4
    lens = np.asarray(lens, np.int32)
    got = paged_latent_attention(jnp.asarray(q, dtype), jnp.asarray(pool, dtype), jnp.asarray(tables),
                                 jnp.asarray(lens), 0.1, v)
    pool, q = (np.asarray(jnp.asarray(a, dtype).astype(jnp.float32)) for a in (pool, q))
    ctx = pool[tables].reshape(B, maxb * bs, hw)
    s = np.einsum("bhc,bsc->bhs", q, ctx[..., :r]) * 0.1
    s = np.where(np.arange(maxb * bs)[None, None] <= lens[:, None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhs,bsc->bhc", p / p.sum(-1, keepdims=True), ctx[..., :v])
    assert got.shape == (B, H, v) and got.dtype == jnp.dtype(dtype)
    return np.asarray(got, np.float32), want


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("edge", ["one_token", "a_step_less_one", "a_step", "a_step_and_one", "two_steps_and_a_page",
                                  "the_windows_last"])
def test_the_kernels_latent_mode_against_the_gathered_formulation(edge, dtype, tol):
    """Interpret mode, around the edges of the kernel's step at this row:
    the edge's slot between an empty neighbour (one token) and ragged ones
    (a page part full, 6 and 13 pages: every power of two a step's wait is
    made of)."""
    from paddle_tpu.ops.pallas.paged_attention import paged_latent_attention, unsupported

    step, bs, maxb = _latent_step(dtype), _KERNEL_CASE["bs"], _KERNEL_CASE["maxb"]
    assert 2 * step + bs <= maxb * bs
    n = {"one_token": 0, "a_step_less_one": step - 1, "a_step": step, "a_step_and_one": step + 1,
         "two_steps_and_a_page": 2 * step + bs - 1, "the_windows_last": maxb * bs - 1}[edge]
    got, want = _latent_kernel_case([0, n, 37, 95, 0, 200], dtype)
    assert np.abs(got - want).max() <= tol
    assert unsupported(0, 16, dtype, latent=(640, 512)) == ""
    assert "whole 128-lane tiles" in unsupported(0, 16, dtype, latent=(576, 512))
    assert "whole 128-lane tiles" in unsupported(0, 16, dtype, latent=(640, 96))
    assert "page of 24 tokens" in unsupported(0, 24, dtype, latent=(640, 512))
    with pytest.raises(ValueError, match="paged_latent_attention: a latent row of 200 lanes"):
        paged_latent_attention(jnp.zeros((1, 2, 160)), jnp.zeros((4, 16, 200)), jnp.zeros((1, 2), jnp.int32),
                               jnp.zeros((1,), jnp.int32), 1.0, 128)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_short_slot_behind_a_long_one_reads_none_of_its_rows(dtype):
    """The short slot's pages land in the buffers the long slot's steps
    filled: the rows behind them are the long slot's still, and count for
    nothing (weights of exactly 0 over finite rows), however loud."""
    step = _latent_step(dtype)
    lens = [2 * step + 40, 5, 3 * 16 + 2, 0]
    quiet, want = _latent_kernel_case(lens, dtype)
    loud, _ = _latent_kernel_case(lens, dtype, loud=(0,))
    np.testing.assert_array_equal(loud[1:], quiet[1:])
    assert np.abs(quiet - want).max() <= (1e-5 if dtype == "float32" else 2e-2)
    assert not np.allclose(loud[0], quiet[0])


def test_the_benchmarks_weight_table_names_what_the_program_reads():
    conf = conf_of("kernel")
    cfg = serving.GPTConfig(**MOD.gpt_config(conf, {"dtype": "float32"}))
    mine, theirs = MOD.param_table(conf), param_table(cfg)
    assert {k: v[0] for k, v in mine.items()} == {k: v[0] for k, v in theirs.items()}
    assert MOD.n_params(conf) == sum(int(np.prod(s)) for s, _ in theirs.values())
    # seven weights of latent attention a layer, under the names the reference reads
    attn = sorted(k for k in mine if k.startswith("gpt.h1.attn."))
    assert attn == [f"gpt.h1.attn.{n}" for n in ("kv_down.w", "kv_norm.scale", "kv_up.w", "proj.w", "q_down.w",
                                                 "q_norm.scale", "q_up.w")]
    assert mine["gpt.h1.attn.kv_down.w"][0] == (64, 128 + 32) and mine["gpt.h1.attn.kv_up.w"][0] == (4, 128, 40)
    assert mine["gpt.h1.attn.q_up.w"][0] == (48, 4 * 48) and mine["gpt.h1.attn.proj.w"][0] == (4 * 24, 64)
    assert "gpt.h0.mlp.gate.w" in mine and "gpt.h0.moe.router.w" not in mine and "gpt.lm_head.w" in mine
    # seed-made weights: the same seed the same arrays, the names and shapes of the table
    a, b = MOD.make_params(conf, 2**31 + 5, "float32"), MOD.make_params(conf, 2**31 + 5, "float32")
    assert {k: v.shape for k, v in a.items()} == {k: v[0] for k, v in mine.items()}
    assert all((np.asarray(a[k]) == np.asarray(b[k])).all() for k in a)
    # gains around 1, the FIRST layer's latent gains around LATENT_GAIN_FIRST
    assert float(jnp.mean(a["gpt.h0.attn.kv_norm.scale"])) == pytest.approx(MOD.LATENT_GAIN_FIRST, abs=0.4)
    assert float(jnp.mean(a["gpt.h1.attn.kv_norm.scale"])) == pytest.approx(1.0, abs=0.2)
    assert float(jnp.std(a["gpt.h0.mlp.down.w"])) == pytest.approx(
        MOD.DENSE_DOWN_GAIN * 0.02 / math.sqrt(6), rel=0.1)
    with pytest.raises(SystemExit, match="no share of 16"):
        MOD.param_table(dict(conf, first_expert_held=15))


def test_a_description_that_cannot_be_served_is_refused_by_name():
    with pytest.raises(NotImplementedError, match=r"recipe 'tp'.*experts"):
        tiny_model(recipe="tp")
    dense = dict(conf_of(), n_layer=1)
    cfg = serving.GPTConfig(**MOD.gpt_config(dense, {"dtype": "float32", "window": 64}))
    with pytest.raises(NotImplementedError, match=r"recipe 'tp'.*latent attention.*no\s+heads to divide"):
        serving.DecodeModel(cfg, recipe="tp", max_batch=2, n_blocks=8)
    with pytest.raises(ValueError, match="one row width"):
        serving.DecodeModel(serving.GPTConfig(n_layer=2, layer_ops=("attn", "latent")), params={})
    with pytest.raises(ValueError, match="no share of 16"):
        serving.GPTConfig(n_experts=16, experts_held=(12, 8))
    with pytest.raises(ValueError, match="equal groups"):
        serving.GPTConfig(n_experts=16, router_groups=3, router_keep_groups=1)
    with pytest.raises(SystemExit, match="topk_method 'noaux_tc' is not built"):
        MOD.gpt_config(dict(CONF, topk_method="noaux_tc"), {})
    # the training graph goes on refusing every block but GPT-2's
    from paddle_tpu.models import gpt

    with pytest.raises(NotImplementedError, match="GPT-2 block only"):
        gpt.build_forward(cfg, None, 1, 8)


def test_the_published_configuration_maps_onto_the_block():
    from benchmark import manifest

    cell = manifest.cell(manifest.load(), "axk1-serve-reason")
    c, e = cell["config"], cell["traffic"]["engine"]
    mod = arch_modules.of(c)
    cfg = serving.GPTConfig(**mod.gpt_config(c, e))
    assert (cfg.d_model, cfg.n_head, cfg.q_lora_rank, cfg.kv_lora_rank) == (7168, 64, 1536, 512)
    assert (cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.latent_row) == (128, 64, 128, 576)
    assert (cfg.mlp_width("moe"), cfg.mlp_width("swiglu"), cfg.d_ff_shared) == (2048, 18432, 2048)
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_per_token, cfg.router_score, cfg.norm_topk,
            cfg.norm_topk_eps, cfg.routed_scale, cfg.router_groups, cfg.router_keep_groups) == (
        192, (0, 12), 8, "sigmoid", True, 1e-20, 2.5, 8, 4)
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.n_layer, cfg.tie_embeddings, cfg.norm, cfg.norm_eps) == (
        20480, 2048, 8, False, "rmsnorm", 1e-6)
    assert cfg.rope_yarn == YarnRope(32.0, 4096, 32.0, 1.0, 1.0, 1.0) and cfg.rope_theta == 10000.0
    assert [cfg.layer_kind(i) for i in range(8)] == [("latent", "swiglu")] + [("latent", "moe")] * 7
    assert (c["n_expert_layers"], c["kv_row_lanes"], c["router_experts"], c["first_expert_held"]) == (7, 640, 192, 0)
    assert c["published"] == {"n_layer": 61, "num_hidden_layers": 61, "n_routed_experts": 192,
                              "vocab_size": 163840}
    # the arithmetic of the configuration file's `bytes`
    attn = 7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384 + 8192 * 7168
    norms = 8 * (2 * 7168 + 1536 + 512) + 7168
    assert attn == 101_122_048 and mod.expert_bytes(c, 1) == 44_040_192
    assert mod.n_params(c) == 7 * (attn + 13 * 44_040_192 + 7168 * 192) + attn + 3 * 7168 * 18432 \
        + 2 * 20480 * 7168 + norms == 5_516_230_656 + norms
    import tools.serve_compile_report as report

    dm = report.abstract_model(cfg, **arch_modules.engine_args(e))
    assert dm.pool_shape() == (8 * 12032, 16, 640) and dm.attention_path() == ("kernel", "")
    assert dm.pool_bytes() == 8 * 12032 * 16 * 1280 and dm.embed_path()[0] == "gather"
    assert mod.kv_token_bytes(c) == 8 * 576 * 2 and dm._no_prev.shape == (96 + 4,)
    # every slot at the longest request keeps its blocks (less the scratch block 0)
    longest = cell["traffic"]["prompt_len"]["hi"] + cell["traffic"]["output_len"]["hi"]
    assert e["max_batch"] * -(-(longest + 1) // e["block_size"]) <= e["n_blocks"] - 1
    assert longest + 1 <= cell["traffic"]["max_total"] <= cfg.max_seq_len


def test_score_shares_the_expanded_form(model, prompt):
    """``score`` runs the trunk prefill runs: the prompt's per-token NLL is
    that of the model's own logits, and so of the reference's."""
    nll, total = model.score(prompt[:20])
    logits = ref_logits(model, prompt[:20])[0][:-1]
    lp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    want = -lp[np.arange(19), prompt[1:20]]
    assert nll.shape == (19,) and np.abs(nll - want).max() <= 1e-3 and total == pytest.approx(want.sum(), abs=1e-2)


def test_a_router_over_replicas_in_this_process_can_fill_their_slots():
    """96 clients against 96 slots: a dispatch holds a thread until its
    answer is whole, so a pool of 64 never filled the batch (PR 50's first
    chip run: 'closed loop never filled the batch')."""
    import types

    def rep(name, slots):
        return serving.LocalReplica(name, types.SimpleNamespace(max_batch=slots))

    assert serving.Router([rep("a", 96)])._pool._max_workers == 192
    assert serving.Router([rep("a", 12)])._pool._max_workers == 64          # the other cells: as before
    assert serving.Router([rep("a", 40), rep("b", 40)])._pool._max_workers == 160
    assert serving.Router([rep("a", 96)], max_workers=8)._pool._max_workers == 8
    assert serving.Router([types.SimpleNamespace(name="http")])._pool._max_workers == 64
