"""The raw-speed round's tentpole: the pallas fused lm-head + CE kernel.

Covers the acceptance surface end to end on the virtual 8-device CPU
mesh (interpret-mode pallas — the same code path the TPU runs compiled):

- forward/backward parity with the reference materialized-logits path
  (fp32 tight, bf16 at the dtype-aware floor), token/vocab padding;
- tp-sharded kernel consistent with the unsharded one on 8 forced-host
  devices (forward, dx and dw), plus the fsdp gather-at-use and pure-dp
  layouts;
- the builder's choice of the loss path by eligibility and
  loss-trajectory parity across all three impls on the GPT train
  program;
- the analytic plan's lmhead_ce_fused_stats term;
- the serving twin's prefill scoring through the same kernel;
- donation: 1-chip and explicit-collectives (mesh-without-recipe)
  programs alias donated params shard-for-shard, bit-equal results;
- the async-loss fit loop: identical dynamics series vs sync mode, the
  deferred-readback counter, exact epoch-tail flush.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.pallas.fused_lmhead_ce import (lmhead_ce,
                                                   lmhead_ce_sharded)


def _ref_nll(x, w, lbl):
    """Materialized logits; a label outside [0, V) picks nothing (what an
    out-of-shard label does under vocab sharding)."""
    logits = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    lbl = lbl.astype(jnp.int32)
    ok = (lbl >= 0) & (lbl < w.shape[0])
    picked = jnp.take_along_axis(
        logits, jnp.where(ok, lbl, 0)[:, None], axis=1)[:, 0]
    return lse - jnp.where(ok, picked, 0.0)


def _data(n, d, v, dtype=jnp.float32, seed=0):
    r = np.random.RandomState(seed)
    x = jnp.asarray(r.randn(n, d) * 0.5, dtype)
    w = jnp.asarray(r.randn(v, d) * 0.5, dtype)
    lbl = jnp.asarray(r.randint(0, v, (n,)), jnp.int32)
    g = jnp.asarray(r.randn(n), jnp.float32)
    return x, w, lbl, g


# ---------------------------------------------------------------------------
# kernel vs reference
# ---------------------------------------------------------------------------

_F32 = dict(rtol=1e-4, atol=1e-5)
_BF16 = dict(rtol=0.05, atol=0.05)
_NLL_F32 = dict(rtol=1e-5, atol=1e-5)
_NLL_BF16 = dict(rtol=2e-3, atol=2e-3)
_DTYPES = [(jnp.float32, _NLL_F32, _F32), (jnp.bfloat16, _NLL_BF16, _BF16)]


def _check_against_reference(x, w, lbl, g, nll_tol, grad_tol, **blocks):
    nll = lmhead_ce(x, w, lbl, **blocks)
    np.testing.assert_allclose(np.asarray(nll), np.asarray(
        _ref_nll(x, w, lbl)), **nll_tol)
    f = lambda x, w: jnp.vdot(lmhead_ce(x, w, lbl, **blocks), g)
    fr = lambda x, w: jnp.vdot(_ref_nll(x, w, lbl), g)
    dx, dw = jax.grad(f, argnums=(0, 1))(x, w)
    dxr, dwr = jax.grad(fr, argnums=(0, 1))(x, w)
    assert dx.dtype == x.dtype and dw.dtype == w.dtype
    np.testing.assert_allclose(np.asarray(dx, np.float32),
                               np.asarray(dxr, np.float32), **grad_tol)
    np.testing.assert_allclose(np.asarray(dw, np.float32),
                               np.asarray(dwr, np.float32), **grad_tol)


# (n, d, v, block_n, block_v); the grid of the fused backward is (token
# blocks, vocab tiles), the dW accumulator lives in HBM and is read back
# once a token block and vocab tile after the first block
_SHAPES = [
    (64, 64, 512, 16, 128),    # 4 x 4: every dW block read back 3 times
    (48, 64, 300, 16, 128),    # tokens AND vocab no tile multiple
    (33, 32, 130, 16, 128),    # 2 vocab tiles: a block is read back one
                               # step after the write-back before it
    (64, 64, 128, 16, 128),    # 1 vocab tile: the block stays resident
    (64, 64, 256, 16, 128),    # ... and the same at two
    (24, 64, 300, 64, 128),    # n < block_n (fsdp4: one token block,
                               # nothing is read back)
    (24, 64, 100, 64, 128),    # one token block x one vocab tile
    (16, 1600, 384, 8, 128),   # GPT-2 XL's width: not a lane multiple
    (40, 64, 300, None, None),  # tiles by shape, clamped to the call
]


@pytest.mark.parametrize("n,d,v,block_n,block_v", _SHAPES)
def test_kernel_matches_reference_fp32(n, d, v, block_n, block_v):
    """Forward + both gradients of the fused backward against the
    materialized-logits path; labels near the padded boundary must not
    pick mask values."""
    x, w, lbl, g = _data(n, d, v)
    _check_against_reference(x, w, lbl, g, _NLL_F32, _F32,
                             block_n=block_n, block_v=block_v)


@pytest.mark.parametrize("n,d,v,block_n,block_v", _SHAPES[:3])
def test_kernel_matches_reference_bf16(n, d, v, block_n, block_v):
    """bf16 inputs at the dtype-aware tolerance floor: the kernel and
    the reference both matmul in bf16 with f32 accumulation, so the
    loss agrees at f32 resolution while grads (cast back to bf16)
    agree at bf16 resolution."""
    x, w, lbl, g = _data(n, d, v, dtype=jnp.bfloat16)
    _check_against_reference(x, w, lbl, g, _NLL_BF16, _BF16,
                             block_n=block_n, block_v=block_v)


@pytest.mark.parametrize("dtype,nll_tol,tol", _DTYPES)
def test_label_in_the_padded_range_picks_nothing(dtype, nll_tol, tol):
    """v = 300 pads to 384 columns: a label of 300..383 (what another
    shard's label can look like under vocab sharding) and a negative one
    match no column: the loss is the logsumexp alone and d_logits the
    softmax alone, in the kernels' last-tile branch and outside it."""
    x, w, lbl, g = _data(48, 64, 300, dtype=dtype, seed=5)
    lbl = lbl.at[3].set(300).at[17].set(383).at[20].set(-84)
    _check_against_reference(x, w, lbl, g, nll_tol, tol,
                             block_n=16, block_v=128)


# the loss and gradients the TWO backward kernels of the parent of PR 40
# (679b9a8: lmhead_ce_dx + lmhead_ce_dw, each forming its own logits
# tile) gave for _data(48, 64, 300, seed=11) at tiles (16, 128): the first
# six losses and their sum, six entries of dx[5] and of dw[lbl[5]], and
# the gradients' absolute sums
_TWO_KERNELS = {
    "float32": {
        "nll": [8.010916709899902, 11.251486778259277, 4.567679405212402,
                9.408699989318848, 7.758273601531982, 2.9175190925598145],
        "nll_sum": 360.6402587890625,
        "dx": [-0.015014749020338058, 0.47870463132858276,
               0.6962459683418274, -0.12962190806865692,
               -0.4277999699115753, -0.13631463050842285],
        "dx_abs": 929.9119873046875,
        "dw": [-1.222535490989685, 1.540732502937317, -0.10366442799568176,
               0.8198438882827759, -0.44570398330688477,
               -0.12794071435928345],
        "dw_abs": 1263.200927734375},
    "bfloat16": {
        "nll": [8.01668643951416, 11.24679946899414, 4.567939281463623,
                9.403664588928223, 7.753928184509277, 2.91546630859375],
        "nll_sum": 360.5628662109375,
        "dx": [-0.01171875, 0.4765625, 0.6953125, -0.12890625,
               -0.427734375, -0.1337890625],
        "dx_abs": 929.9776611328125,
        "dw": [-1.2265625, 1.5390625, -0.103515625, 0.81640625,
               -0.443359375, -0.1279296875],
        "dw_abs": 1263.19677734375},
}


@pytest.mark.parametrize("dtype,nll_tol,tol", _DTYPES)
def test_fused_backward_equals_the_two_kernel_result(dtype, nll_tol, tol):
    want = _TWO_KERNELS[jnp.dtype(dtype).name]
    x, w, lbl, g = _data(48, 64, 300, dtype=dtype, seed=11)
    nll = np.asarray(lmhead_ce(x, w, lbl, block_n=16, block_v=128))
    f = lambda x, w: jnp.vdot(lmhead_ce(x, w, lbl, block_n=16,
                                        block_v=128), g)
    dx, dw = (np.asarray(a, np.float32)
              for a in jax.grad(f, argnums=(0, 1))(x, w))
    np.testing.assert_allclose(nll[:6], want["nll"], **nll_tol)
    np.testing.assert_allclose(nll.sum(), want["nll_sum"], rtol=1e-4)
    np.testing.assert_allclose(dx[5, :6], want["dx"], **tol)
    np.testing.assert_allclose(dw[int(lbl[5]), :6], want["dw"], **tol)
    np.testing.assert_allclose(np.abs(dx).sum(), want["dx_abs"], rtol=2e-3)
    np.testing.assert_allclose(np.abs(dw).sum(), want["dw_abs"], rtol=2e-3)


def test_tiles_follow_the_shape():
    """The dispatcher picks tiles from (tokens, width) and a VMEM budget
    (PR 40's sweep on a v5e): the tallest token block at GPT-2 small's
    width, a narrower vocab tile at GPT-2 XL's (where (1024, 512) ran a
    third slower than (1024, 256)), at GPT-2 small's a vocab tile under
    which the vocabulary pads to the 50,688 rows the benchmark's
    shape-based metric looks for in gpt2s-train-1k, no token block that
    pads a call by more than 1/16."""
    from paddle_tpu.ops.pallas.fused_lmhead_ce import (_VMEM_BUDGET,
                                                       _vmem_bytes, tiles)

    assert tiles(32768, 768, 50304) == (1024, 768)
    assert tiles(1024, 1600, 50304) == (1024, 256)
    assert -(-50304 // 768) * 768 == 50688
    for d in (768, 1600, 4096):
        bn, bv = tiles(32768, d, 50304)
        assert bn % 128 == 0 and _vmem_bytes(bn, bv, d, 2) <= _VMEM_BUDGET
    assert tiles(32768, 4096, 50304)[0] < 1024
    assert tiles(40, 64, 300) == (40, 384)     # clamped to the call
    assert tiles(1500, 768, 50304)[0] == 512   # 1024 would pad 1500 to 2048


def test_kernel_loss_decreases_under_sgd():
    x, w, lbl, _ = _data(64, 32, 256, seed=3)
    def loss(w):
        return jnp.mean(lmhead_ce(x, w, lbl, block_n=32, block_v=128))
    l0 = float(loss(w))
    for _ in range(5):
        w = w - 0.5 * jax.grad(loss)(w)
    assert float(loss(w)) < l0


# ---------------------------------------------------------------------------
# sharded consistency (8 forced-host devices)
# ---------------------------------------------------------------------------


def _sharded_case(mesh_axes, devshape, **kw):
    from jax.sharding import Mesh

    x, w, lbl, g = _data(64, 64, 512)
    base_nll = lmhead_ce(x, w, lbl, block_n=16, block_v=128)
    fr = lambda x, w: jnp.vdot(lmhead_ce(x, w, lbl, block_n=16,
                                         block_v=128), g)
    dxr, dwr = jax.grad(fr, argnums=(0, 1))(x, w)

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(devshape), mesh_axes)
    f = lambda x, w: lmhead_ce_sharded(x, w, lbl, mesh, block_n=16,
                                       block_v=128, **kw)
    nll = jax.jit(f)(x, w)
    dx, dw = jax.jit(jax.grad(
        lambda x, w: jnp.vdot(f(x, w), g), argnums=(0, 1)))(x, w)
    np.testing.assert_allclose(np.asarray(nll), np.asarray(base_nll),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dxr),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dwr),
                               rtol=1e-4, atol=1e-5)


def test_tp_sharded_consistent_with_unsharded():
    """The acceptance bit: vocab-sharded partial stats + pmax/psum
    combine + dx psum reproduce the unsharded kernel on 8 devices."""
    _sharded_case(("dp", "tp"), (2, 4), batch_axes=("dp",),
                  vocab_axis="tp")


def test_fsdp_gather_layout_consistent():
    _sharded_case(("fsdp",), (8,), batch_axes=("fsdp",),
                  gather_axis="fsdp")


def test_pure_dp_layout_consistent():
    _sharded_case(("dp",), (8,), batch_axes=("dp",))


def test_tp_out_of_shard_labels_and_padding():
    """tp over a vocab that pads per shard (512/8 = 64 rows, padded to
    the 128 lane tile): out-of-shard labels land numerically inside the
    padded range and must contribute exactly nothing."""
    from jax.sharding import Mesh

    x, w, lbl, _ = _data(32, 32, 512, seed=7)
    base = lmhead_ce(x, w, lbl, block_n=16, block_v=128)
    mesh = Mesh(np.array(jax.devices()[:8]), ("tp",))
    nll = jax.jit(lambda x, w: lmhead_ce_sharded(
        x, w, lbl, mesh, vocab_axis="tp", block_n=16, block_v=128))(x, w)
    np.testing.assert_allclose(np.asarray(nll), np.asarray(base),
                               rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(nll)).all()


# ---------------------------------------------------------------------------
# the GPT train program: the builder's choice + impl parity
# ---------------------------------------------------------------------------


def _run_gpt(mode, steps=3, vocab=300):
    from paddle_tpu.framework import Executor, Scope, program_guard
    from paddle_tpu.models.gpt import GPTConfig, build_train_program
    from paddle_tpu.optimizer import Adam

    paddle.enable_static()
    try:
        np.random.seed(3)
        cfg = GPTConfig(vocab_size=vocab, n_layer=2, n_head=2, d_model=32,
                        max_seq_len=32, fused_lm_head=mode != "off")
        main, startup, io = build_train_program(cfg, batch=2, seq=16)
        ce_ops = [op for op in main.global_block().ops if op.type == "fused_lm_head_ce"]
        if mode == "chunked":  # the op's own loop, asked for as tools/ce_sweep.py asks for its tiles
            ce_ops[0]._set_attr("impl", "chunked")
        with program_guard(main, startup):
            Adam(learning_rate=1e-3).minimize(io["loss"])
        scope = Scope()
        exe = Executor()
        exe.run(startup, scope=scope)
        r = np.random.RandomState(0)
        feed = {"tokens": r.randint(0, vocab, (2, 16)).astype(np.int64),
                "labels": r.randint(0, vocab, (2, 16)).astype(np.int64)}
        losses = [float(exe.run(main, feed=feed, fetch_list=[io["loss"]],
                                scope=scope)[0]) for _ in range(steps)]
        return [io["lm_head_impl"]] + [op.attr("impl") for op in ce_ops], losses
    finally:
        paddle.disable_static()


def test_train_program_impl_parity():
    """All three loss paths train the same curve (the fused paths never
    materialize logits; the loss must not notice)."""
    impl_p, lp = _run_gpt("pallas")
    impl_c, lc = _run_gpt("chunked")
    impl_o, lo = _run_gpt("off")
    assert (impl_p, impl_c, impl_o) == (["pallas", "pallas"], ["pallas", "chunked"], ["off"])
    np.testing.assert_allclose(lp, lc, rtol=2e-4)
    np.testing.assert_allclose(lp, lo, rtol=2e-4)
    assert lp[-1] < lp[0]


def test_flag_resolution():
    """The builder's choice, by what the config says: the kernels where
    the head is tied and the graph unpipelined, the materialized logits
    otherwise or where the caller wants them; no environment, no modes."""
    from paddle_tpu.models.gpt import GPTConfig, resolve_lm_head_impl

    small = dict(vocab_size=64, n_layer=2, n_head=1, d_model=16)
    assert resolve_lm_head_impl(GPTConfig(**small)) == "pallas"
    assert resolve_lm_head_impl(GPTConfig(**small, fused_lm_head=False)) == "off"
    assert resolve_lm_head_impl(GPTConfig(**small, tie_embeddings=False)) == "off"
    assert resolve_lm_head_impl(GPTConfig(**small, pp_stages=2)) == "off"
    for legacy in ("pallas", "chunked", "auto", None, 1):
        with pytest.raises(ValueError, match="fused_lm_head must be True or False"):
            resolve_lm_head_impl(GPTConfig(**small, fused_lm_head=legacy))


def test_env_flag_declared_and_documented():
    from paddle_tpu import flags

    defs = flags.env_flag_defs()
    for name in ("PADDLE_TPU_ASYNC_LOSS", "PADDLE_TPU_MEMWATCH_SAMPLE_RUNS"):
        assert name in defs and defs[name]["help"], name


# ---------------------------------------------------------------------------
# the analytic plan's fused-lmhead term
# ---------------------------------------------------------------------------


def test_predicted_collectives_lmhead_term():
    from paddle_tpu.parallel import recipes

    params = [("gpt.wte", (1024, 64), 4)]
    tp = recipes.resolve_recipe("tp", 8)
    chunked = tp.predicted_collectives(params, batch=16, seq=32,
                                       d_model=64, n_layer=2)
    fused = tp.predicted_collectives(params, batch=16, seq=32,
                                     d_model=64, n_layer=2,
                                     lmhead="pallas")
    act = 16 * 32 * 64 * 4
    stats = 3 * 16 * 32 * 4
    assert chunked["by_kind"]["all-reduce"] == (4 * 2 + 4) * act
    assert fused["by_kind"]["all-reduce"] == (4 * 2 + 3) * act + stats
    terms = {i["term"] for i in fused["instructions"]}
    assert "lmhead_ce_fused_stats" in terms
    # instruction payloads still sum to the by-kind totals
    assert sum(i["payload_bytes"] for i in fused["instructions"]) == \
        fused["payload_bytes_total"]


# ---------------------------------------------------------------------------
# serving twin: prefill scoring through the same kernel
# ---------------------------------------------------------------------------


def test_serving_score_matches_naive_logits():
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving.model import DecodeModel

    cfg = GPTConfig(vocab_size=128, n_layer=2, n_head=2, d_model=32,
                    max_seq_len=128)
    m = DecodeModel(cfg, seed=0)
    toks = np.random.RandomState(1).randint(0, 128, (20,))
    nll, total = m.score(toks)
    assert nll.shape == (19,)
    assert np.isclose(total, nll.sum(), rtol=1e-5)

    # reference: greedy prefill hidden states -> naive logits NLL
    import jax.numpy as jnp
    p = m.params
    L = 20
    pos = np.arange(L)
    x = p["gpt.wte"][toks] + p["gpt.wpe"][pos]
    x = jnp.asarray(x)[None]
    causal = jnp.asarray(pos[:, None] >= pos[None, :])
    import math as _math
    scale = 1.0 / _math.sqrt(cfg.head_dim)
    for i in range(cfg.n_layer):
        ln = f"gpt.h{i}"
        h = m._ln_p(p, x, f"{ln}.ln1")
        q = m._linear(p, h, f"{ln}.attn.q").reshape(1, L, 2, 16)
        k = m._linear(p, h, f"{ln}.attn.k").reshape(1, L, 2, 16)
        v = m._linear(p, h, f"{ln}.attn.v").reshape(1, L, 2, 16)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        s = jnp.where(causal[None, None], s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(1, L, -1)
        x = x + m._linear(p, o, f"{ln}.attn.proj")
        x = x + m._mlp(p, m._ln_p(p, x, f"{ln}.ln2"), ln)
    x = m._ln_p(p, x, "gpt.lnf")
    ref = np.asarray(_ref_nll(x[0, :L - 1], jnp.asarray(p["gpt.wte"]),
                              jnp.asarray(toks[1:], jnp.int32)))
    np.testing.assert_allclose(nll, ref, rtol=1e-4, atol=1e-4)
