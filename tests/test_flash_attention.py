"""Pallas flash-attention kernel parity vs the XLA sdpa reference.

On the CPU test platform the kernel runs in the pallas interpreter, so the
exact same kernel code the TPU compiles is what is checked here (the
reference repo's analogous rigor: operators/jit/ refer-vs-gen kernel
parity tests). Checks forward and backward (custom_vjp flash backward)
against jax.vjp through the einsum path, causal and full, plus the
dispatcher integration in fused_attention_tpu.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops.attention import _sdpa_xla  # noqa: E402
from paddle_tpu.ops.pallas.flash_attention import flash_attention, fused_bwd_fits  # noqa: E402


def _rand_qkv(b, h, t, d, dtype, seed=0):
    r = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(r.randn(b, h, t, d).astype("float32"), dtype=dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_xla(causal):
    q, k, v = _rand_qkv(2, 2, 512, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = _sdpa_xla(q, k, v, is_causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_forward_bf16():
    q, k, v = _rand_qkv(1, 2, 256, 64, jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = _sdpa_xla(q, k, v, is_causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2
    )


# The ONE fused backward kernel (PR 43: dq, dk and dv from one rematerialised
# score tile; BTHD, causal, T == Tk) as bwd_blocks = ("fused", bk): kv tiles of
# bk columns against the whole sequence's q rows, trimmed to those under the
# diagonal (dk, dv whole after a kv tile's one step, dq accumulated over the
# sequence); one tile, two, three, four and eight, heads of 64 and of 128.
_FUSED_BWD = {  # name: (T, head size, bk[, heads])
    "t256_bk128": (256, 64, 128), "t256_bk128_d128": (256, 128, 128), "t256_bk256": (256, 64, 256),
    "t256_bk256_d128": (256, 128, 256), "t512_bk128": (512, 64, 128), "t512_bk256": (512, 64, 256),
    "t512_bk512": (512, 64, 512), "t512_bk256_d128": (512, 128, 256), "t768_bk256": (768, 64, 256),
    "t1024_bk128": (1024, 64, 128), "t1024_bk256": (1024, 64, 256), "t1024_bk512": (1024, 64, 512),
    # more than two groups of heads (two heads of 64 fill a lane tile): the kernel LOOPS over them, two an
    # iteration where they pair up; four heads of 64 are the two groups of ONE unrolled iteration
    "t256_bk256_4_heads": (256, 64, 256, 4), "t256_bk256_6_heads": (256, 64, 256, 6),
    "t512_bk256_8_heads": (512, 64, 256, 8), "t256_bk256_3_heads_d128": (256, 128, 256, 3),
    "t512_bk256_4_heads_d128": (512, 128, 256, 4),
}


@pytest.mark.parametrize("causal", [False, True] + sorted(_FUSED_BWD))
def test_backward_matches_xla(causal):
    if causal in _FUSED_BWD:
        t, d, bk, *heads = _FUSED_BWD[causal]
        q, k, v = _qkv("BTHD", t, t, seed=1, h=heads[0] if heads else 1 if t == 1024 else 2, d=d)
        flash = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=True, block_q=128, block_k=128, layout="BTHD", bwd_blocks=("fused", bk))
        ref = lambda q, k, v: _sdpa_xla(q, k, v, is_causal=True, layout="BTHD")  # noqa: E731
    else:
        q, k, v = _rand_qkv(1, 2, 256, 64, jnp.float32, seed=1)
        flash = lambda q, k, v: flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)  # noqa: E731
        ref = lambda q, k, v: _sdpa_xla(q, k, v, is_causal=causal)  # noqa: E731

    g_flash = jax.grad(lambda *a: (flash(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: (ref(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch",
        )


@pytest.mark.parametrize("t,bk", [(256, 128), (512, 256)])
def test_fused_backward_in_bf16_is_as_close_to_xla_as_the_two_kernels(t, bk):
    """bf16 operands, f32 accumulation, p and ds cast to bf16 at the matmul,
    as in the two kernels: against the f32 reference the fused kernel's
    gradients are no further off than theirs."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv("BTHD", t, t, seed=5))
    want = jax.grad(lambda *a: (_sdpa_xla(*a, is_causal=True, layout="BTHD") ** 2).sum(), argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))

    def grads(bwd_blocks):
        return jax.grad(lambda *a: (flash_attention(
            *a, causal=True, block_q=128, block_k=128, layout="BTHD",
            bwd_blocks=bwd_blocks).astype(jnp.float32) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)

    err = lambda got: [float(np.abs(np.asarray(g, np.float32) - np.asarray(w)).max())  # noqa: E731
                       for g, w in zip(got, want)]
    two, one = err(grads((128, 256, 256, 128))), err(grads(("fused", bk)))
    scale = [float(np.abs(np.asarray(w)).max()) for w in want]
    for e2, e1, s in zip(two, one, scale):
        assert e1 <= max(1.5 * e2, 2e-2 * s), (two, one, scale)


def test_the_fused_backward_refuses_what_it_cannot_run():
    q, k, v = _qkv("BTHD", 256, 256, seed=0)
    with pytest.raises(ValueError, match="fused backward"):
        flash_attention(q, k, v, causal=False, layout="BTHD", bwd_blocks=("fused", 128))
    with pytest.raises(ValueError, match="fused backward"):
        flash_attention(*_qkv("BHTD", 256, 256, seed=0), causal=True, bwd_blocks=("fused", 128))
    with pytest.raises(ValueError, match="fused backward"):
        flash_attention(q, *_qkv("BTHD", 256, 512, seed=0)[1:], causal=True, layout="BTHD",
                        bwd_blocks=("fused", 256))
    with pytest.raises(ValueError, match="fused backward"):  # its q tile is the sequence: one number, the kv tile
        flash_attention(q, k, v, causal=True, layout="BTHD", bwd_blocks=("fused", 256, 128))
    with pytest.raises(ValueError, match="must divide"):
        flash_attention(q, k, v, causal=True, layout="BTHD", bwd_blocks=("fused", 96))
    with pytest.raises(ValueError, match="whole lane tiles"):  # its loop over heads slices lanes at its index
        flash_attention(*_qkv("BTHD", 256, 256, seed=0, h=3), causal=True, layout="BTHD", bwd_blocks=("fused", 128))
    with pytest.raises(ValueError, match="odd multiples of 128"):  # Mosaic has no such load (v5e, PR 43's sweep)
        flash_attention(*_qkv("BTHD", 512, 512, seed=0, h=6), causal=True, layout="BTHD", bwd_blocks=("fused", 128))


def test_causal_cross_attention_alignment():
    """Tq != Tk with causal: the kernel must use the same bottom-right
    alignment as _sdpa_xla's tril(tk - tq) (review finding r2)."""
    r = np.random.RandomState(7)
    q = jnp.asarray(r.randn(1, 2, 128, 64).astype("float32"))
    k = jnp.asarray(r.randn(1, 2, 384, 64).astype("float32"))
    v = jnp.asarray(r.randn(1, 2, 384, 64).astype("float32"))
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = _sdpa_xla(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=128, block_k=128) ** 2).sum()

    def loss_ref(q, k, v):
        return (_sdpa_xla(q, k, v, is_causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch",
        )


def test_uneven_seq_blocks():
    # 384 = 3 x 128 blocks, q/k lengths differ (cross attention, non-causal)
    r = np.random.RandomState(3)
    q = jnp.asarray(r.randn(1, 2, 256, 64).astype("float32"))
    k = jnp.asarray(r.randn(1, 2, 384, 64).astype("float32"))
    v = jnp.asarray(r.randn(1, 2, 384, 64).astype("float32"))
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    ref = _sdpa_xla(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layout", ["BHTD", "BTHD"])
def test_dispatcher_takes_flash_path(monkeypatch, layout):
    """fused_attention_tpu with a long causal sequence (>=1024, the
    measured v5e crossover vs the XLA path) must route through the pallas
    kernel (not silently fall back), in both head layouts."""
    import sys

    from paddle_tpu.framework.registry import LoweringContext, get_op_def

    called = {}
    real = flash_attention

    def spy(*a, **kw):
        called["hit"] = True
        return real(*a, **kw)

    monkeypatch.setattr(
        sys.modules["paddle_tpu.ops.pallas.flash_attention"], "flash_attention", spy
    )
    q, k, v = _rand_qkv(1, 2, 1024, 64, jnp.float32)
    if layout == "BTHD":
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    opdef = get_op_def("fused_attention_tpu")
    out = opdef.lower(
        LoweringContext(rng_key=jax.random.key(0)),
        {"Q": [q], "K": [k], "V": [v]},
        {"is_causal": True, "is_test": True, "layout": layout},
    )["Out"]
    assert called.get("hit"), "dispatcher fell back to XLA path"
    ref = _sdpa_xla(q, k, v, is_causal=True, layout=layout)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_bthd_layout_matches_bhtd(causal):
    """Native BTHD tiling (no transposes in the graph) must agree with
    the BHTD kernel, forward and backward."""
    q, k, v = _rand_qkv(2, 2, 256, 64, jnp.float32, seed=2)
    qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))

    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    out_t = flash_attention(
        qt, kt, vt, causal=causal, block_q=128, block_k=128, layout="BTHD"
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(out_t.transpose(0, 2, 1, 3)),
        rtol=2e-5, atol=2e-5,
    )

    def loss_b(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=128, block_k=128) ** 2).sum()

    def loss_t(q, k, v):
        return (
            flash_attention(
                q, k, v, causal=causal, block_q=128, block_k=128, layout="BTHD"
            ) ** 2
        ).sum()

    g_b = jax.grad(loss_b, argnums=(0, 1, 2))(q, k, v)
    g_t = jax.grad(loss_t, argnums=(0, 1, 2))(qt, kt, vt)
    for gb, gt_, name in zip(g_b, g_t, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gb), np.asarray(gt_.transpose(0, 2, 1, 3)),
            rtol=2e-4, atol=2e-4, err_msg=f"d{name} mismatch",
        )


def test_bwd_blocks_decoupled_grad_parity():
    """Separate dq/dkv tilings must produce the same gradients as the
    shared-tiling default (and as the XLA reference)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import _sdpa_xla
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(1, 4, 256, 64), jnp.float32)
    k = jnp.asarray(r.randn(1, 4, 256, 64), jnp.float32)
    v = jnp.asarray(r.randn(1, 4, 256, 64), jnp.float32)

    def loss_flash(q, k, v, bwd_blocks):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128,
            interpret=True, bwd_blocks=bwd_blocks,
        ).astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_sdpa_xla(q, k, v, is_causal=True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for bwd in (None, (64, 256, 256, 64)):
        g = jax.grad(lambda a, b, c: loss_flash(a, b, c, bwd),
                     argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-3, atol=2e-3)


# -- the tiles the dispatcher picks (PR 35): the causal schedule at the shape
# the benchmark cell gpt2s-train-1k runs, scaled in batch and heads for the
# interpreter --------------------------------------------------------------


def _tiles_total():
    """{(kernel, cls): count} of flash_tiles_total as it stands."""
    import sys

    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]  # the package attribute is the function
    return {(k, c): n for k, by_cls in fa.tile_counts().items() for c, n in by_cls.items()}


def _tiles_of(fn, *args):
    """Tiles by (kernel, cls) that tracing `fn` (nothing runs) counts."""
    before = _tiles_total()
    jax.make_jaxpr(fn)(*args)
    after = _tiles_total()
    return {key: after[key] - before[key] for key in after}


def _attend(layout, causal, **attrs):
    """The op as the dispatcher lowers it: its own default tiles, unless
    `attrs` holds a sweep's."""
    from paddle_tpu.framework.registry import LoweringContext, get_op_def

    opdef = get_op_def("fused_attention_tpu")

    def f(q, k, v):
        return opdef.lower(
            LoweringContext(rng_key=jax.random.key(0)), {"Q": [q], "K": [k], "V": [v]},
            {"is_causal": causal, "is_test": True, "layout": layout, **attrs})["Out"]
    return f


def _qkv(layout, t, tk, seed, b=1, h=2, d=64):
    r = np.random.RandomState(seed)
    shape = lambda n: (b, n, h, d) if layout == "BTHD" else (b, h, n, d)  # noqa: E731
    return tuple(jnp.asarray(r.randn(*shape(n)).astype("float32")) for n in (t, tk, tk))


_DISPATCH_CASES = {"causal": (1024, 1024, True), "causal_tk_1536": (1024, 1536, True),
                   "causal_tk_1152": (1024, 1152, True), "full": (1024, 1024, False),
                   # PR 43: a causal BTHD call of equal lengths up to 1024 takes the ONE fused backward
                   "causal_d128": (1024, 1024, True, {"d": 128, "h": 1}),
                   "causal_t512": (512, 512, True, {}), "causal_t256_d128": (256, 256, True, {"d": 128}),
                   "causal_t768": (768, 768, True, {}),
                   # ... unless its heads do not fit that kernel: three heads of 64 are no whole lane tiles, and the
                   # call falls back to the table's dq (one step, eight trimmed parts) and dkv (tall tiles)
                   "causal_3_heads": (1024, 1024, True, {"h": 3})}


@pytest.mark.parametrize("case", sorted(_DISPATCH_CASES))
@pytest.mark.parametrize("layout", ["BTHD", "BHTD"])
def test_dispatchers_tiles_match_xla(monkeypatch, layout, case):
    """T 1024, heads of 64, the tiles the dispatcher picks by itself:
    forward and dq / dk / dv against the XLA reference; with Tk > T the
    mask is bottom-right aligned (offset 512: whole tiles; 128: the kv
    tile falls to 128 and the backward takes the forward's tiles)."""
    from paddle_tpu.ops import attention

    t, tk, causal, *shape = _DISPATCH_CASES[case]
    if t < 1024:  # the XLA path runs below the op's crossover otherwise
        monkeypatch.setattr(attention, "FLASH_MIN_SEQ", t)
    q, k, v = _qkv(layout, t, tk, seed=11, **(shape[0] if shape else {}))
    flash = _attend(layout, causal)
    ref = lambda q, k, v: _sdpa_xla(q, k, v, is_causal=causal, layout=layout)  # noqa: E731
    n0, tiles0 = attention.FLASH_DISPATCH_COUNT, _tiles_total()
    out, vjp = jax.vjp(flash, q, k, v)
    assert attention.FLASH_DISPATCH_COUNT == n0 + 1, "dispatcher fell back to the XLA path"
    want, vjp_ref = jax.vjp(ref, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)
    cot = 2.0 * want
    for got, exp, name in zip(vjp(cot), vjp_ref(cot), "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch")
    # which backward ran: the fused kernel counts under dkv and leaves dq alone
    now = _tiles_total()
    ran = {kern: sum(now[kern, c] - tiles0[kern, c] for c in ("skipped", "interior", "diagonal")) > 0
           for kern in ("dq", "dkv")}
    h, d = q.shape[2 if layout == "BTHD" else 1], q.shape[-1]
    fused = layout == "BTHD" and causal and t == tk and fused_bwd_fits(256, t, h, d)
    assert ran == {"dq": not fused, "dkv": True}, ran
    assert fused == (attention._flash_tiles(t, tk, layout, causal, h, d)[2] == ("fused", 256))


_FUSED, _TWO = ("fused", 256), (128, 1024, 512, 256)


@pytest.mark.parametrize("call,want", [
    # (tq, tk, layout, causal, heads, head size) -> bwd_blocks: the fused kernel where the call is causal BTHD
    # of equal lengths up to 1024 that 256 divides, its blocks and accumulator fit the VMEM budget (32 heads of 64
    # do, the widest the chip ran; 40 do not) and its heads group into whole lane tiles; the two kernels' tiles otherwise
    ((1024, 1024, "BTHD", True, 12, 64), _FUSED), ((512, 512, "BTHD", True, 12, 64), _FUSED),
    ((768, 768, "BTHD", True, 12, 64), _FUSED), ((1024, 1024, "BTHD", True, 6, 128), _FUSED),
    ((384, 384, "BTHD", True, 12, 64), (128, 128, 128, 128)), ((256, 256, "BTHD", True, 12, 64), _FUSED),
    ((1024, 1024, "BTHD", True, 16, 64), _FUSED), ((1024, 1024, "BTHD", True, 20, 64), _FUSED),
    ((1024, 1024, "BTHD", True, 24, 64), _FUSED), ((1024, 1024, "BTHD", True, 25, 64), _TWO),
    ((1024, 1024, "BTHD", True, 32, 64), _FUSED), ((1024, 1024, "BTHD", True, 40, 64), _TWO),
    ((1024, 1024, "BTHD", True, 16, 128), _FUSED), ((1024, 1024, "BTHD", True, 7, 64), _TWO),
    ((1024, 1024, "BTHD", True, 3, 64), _TWO), ((1024, 1024, "BTHD", True, 1, 64), _FUSED),
    ((1024, 1536, "BTHD", True, 12, 64), (512, 512, 512, 512)), ((2048, 2048, "BTHD", True, 12, 64), (512, 512, 512, 512)),
    ((1024, 1024, "BTHD", False, 12, 64), (512, 512, 512, 512)), ((1024, 1024, "BHTD", True, 12, 64), (512, 1024, 512, 1024)),
])
def test_the_table_takes_the_fused_backward_where_the_call_allows_it(call, want):
    from paddle_tpu.ops import attention

    assert attention._flash_tiles(*call)[2] == want


def test_the_ops_own_tiles_come_before_the_tables_and_the_sweep_refuses_a_malformed_spec():
    """tools/flash_sweep.py step names a tiling as the op's block_q /
    block_k / bwd_blocks attributes: read from what flash_tiles_total
    counts at T 1024 (squares of a tile's smaller side, two planes). The
    forward's own tiles tie the backward to them; `bwd_blocks` of one kv
    tile is the ONE fused kernel (counted under dkv, dq nothing), of four
    dq's and dkv's."""
    import os
    import sys

    a = jax.ShapeDtypeStruct((2, 1024, 2, 64), jnp.bfloat16)

    def squares(**attrs):
        f = _attend("BTHD", True, **attrs)
        n = _tiles_of(lambda q, k, v: jax.vjp(f, q, k, v)[1](q), a, a, a)
        return tuple(sum(n[kern, c] for c in ("skipped", "interior", "diagonal")) for kern in ("fwd", "dq", "dkv"))

    assert squares() == (2 * 16, 0, 2 * 16)  # the table: forward 256 x 1024, fused kv tiles of 256
    assert squares(block_q=512, block_k=512) == (2 * 4, 2 * 4, 2 * 4)
    assert squares(bwd_blocks=[512]) == (2 * 16, 0, 2 * 4)
    assert squares(block_q=512, block_k=512, bwd_blocks=[128, 1024, 512, 256]) == (2 * 4, 2 * 64, 2 * 16)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
    import flash_sweep

    assert flash_sweep.step_attrs("-") == {}
    assert flash_sweep.step_attrs("512,512") == {"block_q": 512, "block_k": 512}
    assert flash_sweep.step_attrs("256,1024/fused,256") == {"block_q": 256, "block_k": 1024, "bwd_blocks": [256]}
    assert flash_sweep.step_attrs("/128,1024,512,256") == {"bwd_blocks": [128, 1024, 512, 256]}
    for bad in ("fused,256", "256,1024/fused,1024,256", "256,1024/128,1024", "256", "256;1024/128,1024;512,256", ""):
        with pytest.raises(ValueError, match="--tiles"):
            flash_sweep.step_attrs(bad)
    with pytest.raises(ValueError, match="--tiles '256,1024/fused'"):  # before a process is spent on it
        flash_sweep.main(["step", "--tiles", "- 256,1024/fused"])


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv", "bwd"])
@pytest.mark.parametrize("layout", ["BTHD", "BHTD"])
def test_flash_tiles_total_says_what_the_causal_schedule_computes(layout, kernel):
    """The counter is bumped where a call is traced into a program, over
    the call's whole grid, in squares of a tile's smaller side. At T 1024
    causal every BTHD kernel computes at most 10 of 16 parts of the score
    square (the tiles before PR 35: forward all of it, backward three
    quarters); the BHTD forward's one square tile computes all of it and
    its backward three quarters; a non-causal call computes all of it,
    every tile interior; at T 2048 whole tiles lie below the diagonal."""
    def traced(t, causal, b=2, h=2, kernel=kernel):
        shape = (b, t, h, 64) if layout == "BTHD" else (b, h, t, 64)
        a = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        f = _attend(layout, causal)
        n = _tiles_of(lambda q, k, v: jax.vjp(f, q, k, v)[1](q), a, a, a)
        return {c: n[kernel, c] for c in ("skipped", "interior", "diagonal")}

    def share(n):
        return (n["interior"] + n["diagonal"]) / max(sum(n.values()), 1)

    # since PR 43 the BTHD backward at T 1024 causal is ONE fused kernel,
    # counted under dkv (its name): 10 of 16 parts, and dq counts nothing
    want = {"BTHD": {"fwd": 0.625, "dq": 0.0, "dkv": 0.625},
            "BHTD": {"fwd": 1.0, "dq": 0.75, "dkv": 0.75}}[layout]
    if kernel == "bwd":  # the pair one backward call counts, at both lengths
        dq, dkv = (traced(1024, True, kernel=k) for k in ("dq", "dkv"))
        assert (share(dq), share(dkv)) == (want["dq"], want["dkv"]), (dq, dkv)
        assert (sum(dq.values()) == 0) == (layout == "BTHD")
        assert layout == "BHTD" or sum(dkv.values()) == 2 * 16  # two planes of 4 x 4 squares of 256
        dq, dkv = (traced(2048, True, kernel=k) for k in ("dq", "dkv"))
        assert sum(dq.values()) == sum(dkv.values()) > 0 and share(dq) == share(dkv) <= 0.75
        return
    want = want[kernel]
    at_1k = traced(1024, True)
    assert share(at_1k) == want, at_1k
    # the grid's planes are counted: batch, and heads where the grid walks them
    assert sum(at_1k.values()) % (2 if layout == "BTHD" else 4) == 0
    again = traced(1024, True)  # a second call site counts again, whatever jit has cached
    assert again == at_1k
    full = traced(1024, False)
    assert share(full) == 1.0 and full["diagonal"] == 0 and full["skipped"] == 0, full
    at_2k = traced(2048, True)
    assert at_2k["interior"] > 0 and at_2k["skipped"] > 0 and share(at_2k) <= 0.75, at_2k


_SCHEDULES = [(256, 1024, 1024, 1024), (512, 1024, 1024, 1024), (1024, 256, 1024, 1024), (256, 256, 1024, 1024),
              (512, 512, 1024, 1024), (256, 1024, 2048, 2048), (1024, 512, 2048, 2048), (256, 512, 1024, 1536),
              (256, 128, 1024, 1152), (256, 512, 1024, 1152 + 128 * 3), (128, 512, 512, 1024), (256, 512, 1024, 512),
              # PR 43: the fused backward's tiles (a q tile as long as the sequence), and what its sweep tried
              (1024, 512, 1024, 1024), (1024, 128, 1024, 1024), (128, 1024, 1024, 1024), (512, 256, 1024, 1024),
              (512, 256, 512, 512), (256, 128, 256, 256), (128, 512, 512, 512), (768, 256, 768, 768)]


@pytest.mark.parametrize("sweep", ["kv", "q"])
@pytest.mark.parametrize("bq,bk,t,tk", _SCHEDULES)
def test_the_causal_schedule_computes_every_score_the_mask_keeps(bq, bk, t, tk, sweep):
    """The schedule as the kernels decide it (skipped / interior / a tile
    crossing the diagonal trimmed to one of its static parts), replayed on
    the host against the mask itself: no score the mask keeps is left out,
    a wide or tall tile whose crossing is aligned computes no granule that
    is all mask, and `_tile_classes` (what flash_tiles_total counts) is
    the area computed. `sweep`: the forward and dq sweep kv tiles, dkv q
    tiles; a sweep of several steps keeps a tile of more than two parts
    whole."""
    import sys

    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    nq, nk, offset, g = t // bq, tk // bk, tk - t, min(bq, bk)
    keep = np.tril(np.ones((t, tk), bool), offset)
    computed = np.zeros((t, tk), bool)
    trims = fa._trims(bq, bk, offset, nk if sweep == "kv" else nq)
    assert len(trims) in (1, max(bq, bk) // g)
    n_interior = n_diagonal = 0
    for iq in range(nq):
        for ik in range(nk):
            cross = iq * bq + offset - ik * bk
            run, full = cross + bq - 1 >= 0, cross >= bk - 1
            if not run:
                continue
            r0, r1, c0, c1 = (0, bq, 0, bk) if full or len(trims) == 1 else trims[abs(cross) // g]
            tile = computed[iq * bq:(iq + 1) * bq, ik * bk:(ik + 1) * bk]
            assert not tile.any()
            tile[r0:r1, c0:c1] = True
            if full:
                assert keep[iq * bq:(iq + 1) * bq, ik * bk:(ik + 1) * bk].all()
                n_interior += (bq // g) * (bk // g)
            else:
                n_diagonal += (r1 - r0) * (c1 - c0) // g ** 2
    assert not (keep & ~computed).any(), "a score the mask keeps is never computed"
    skipped, interior, diagonal = fa._tile_classes(nq, nk, bq, bk, offset, True, trims)
    assert (interior, diagonal) == (n_interior, n_diagonal)
    assert (interior + diagonal) * g * g == computed.sum() and skipped * g * g == (~computed).sum()
    if len(trims) > 1:  # aligned: every computed granule holds a score the mask keeps
        blocks = (keep & computed).reshape(t // g, g, tk // g, g).any(axis=(1, 3))
        assert blocks.sum() == interior + diagonal
    assert fa._tile_classes(nq, nk, bq, bk, offset, False, trims) == (0, t * tk // g ** 2, 0)


@pytest.mark.parametrize("bq,bk,t,tk", [(256, 256, 1024, 1024), (128, 256, 1024, 1024), (256, 128, 1024, 1152),
                                        (256, 256, 1024, 1536), (512, 256, 2048, 2048), (256, 512, 1024, 512)])
def test_a_skipped_step_names_the_block_its_neighbour_holds(bq, bk, t, tk):
    """The sequential sweep's block index under the causal mask: a tile
    that runs fetches its own block; a skipped one names the block of the
    nearest tile of its row (column) that runs, so consecutive skipped
    steps change no index and cost no copy. Tk < T leaves rows with
    nothing to attend: their steps all name block 0."""
    import sys

    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]  # the package attribute is the function
    nq, nk, offset = t // bq, tk // bk, tk - t
    run = lambda iq, ik: iq * bq + bq - 1 + offset >= ik * bk  # noqa: E731
    qi, ki = fa._tile_index(True, False, bq, bk, nq, nk, offset)
    for iq in range(nq):
        runs = [ik for ik in range(nk) if run(iq, ik)]
        for ik in range(nk):
            assert int(qi(iq, ik)) == iq
            assert int(ki(iq, ik)) == (ik if run(iq, ik) else max(runs, default=0)), (iq, ik)
    qi, ki = fa._tile_index(True, True, bq, bk, nq, nk, offset)
    for ik in range(nk):
        runs = [iq for iq in range(nq) if run(iq, ik)]
        for iq in range(nq):
            assert int(ki(ik, iq)) == ik
            assert int(qi(ik, iq)) == (iq if run(iq, ik) else min(runs, default=nq - 1)), (iq, ik)
    # non-causal: the bare indices, as before
    qi, ki = fa._tile_index(False, False, bq, bk, nq, nk, offset)
    assert (qi(2, 3), ki(2, 3)) == (2, 3)
    qi, ki = fa._tile_index(False, True, bq, bk, nq, nk, offset)
    assert (qi(2, 3), ki(2, 3)) == (3, 2)


def test_flash_tiles_total_reaches_the_report_and_the_scrape():
    from paddle_tpu import monitor
    from tools import obs_report

    a = jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.bfloat16)
    f = lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=256, block_k=256,  # noqa: E731
                                        layout="BTHD")
    jax.make_jaxpr(lambda q, k, v: jax.vjp(f, q, k, v)[1](q))(a, a, a)
    section = obs_report._executor_section(monitor.snapshot())["flash_tiles"]
    assert set(section) == {"fwd", "dq", "dkv"}
    now = _tiles_total()
    for kernel, tiles in section.items():
        for cls in ("skipped", "interior", "diagonal"):
            assert tiles[cls] == now[kernel, cls]
        assert 0.0 < tiles["computed_share"] <= 1.0
    prom = monitor.to_prometheus()
    assert 'flash_tiles_total{kernel="fwd",cls="diagonal"}' in prom or \
        'flash_tiles_total{cls="diagonal",kernel="fwd"}' in prom


# ---- the causal one-step BTHD forward LOOPS over its head groups (PR 53) ----
#
# Where the kv sweep is one step and the heads group into whole 128-lane tiles,
# the forward walks groups of heads with a fori_loop (two groups an iteration
# where they pair up) and writes a group's output and lse from its own values;
# everything else keeps the body that unrolls its heads. flash_fwd_calls_total
# says which a call took.


def _fa():
    import sys

    return sys.modules["paddle_tpu.ops.pallas.flash_attention"]


def _scores_reference(q, k, v):
    """(out, lse) of causal BTHD attention through materialised scores."""
    from tools.flash_sweep import scores_reference

    return scores_reference(q, k, v)


def _bthd_qkv(h, d, t, tk=None, b=1, seed=0):
    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(r.randn(b, n, h, d), jnp.float32) for n in (t, tk or t, tk or t))


def _forward(q, k, v, bq, bk):
    fa = _fa()
    before = fa.fwd_body_counts()
    out, res = fa._flash_fwd(q, k, v, True, 1.0 / np.sqrt(q.shape[-1]), min(bq, q.shape[1]), min(bk, k.shape[1]),
                             True, True, None)
    took = {b: n - before[b] for b, n in fa.fwd_body_counts().items() if n != before[b]}
    return out, res[4], took


_LOOPED = {  # name: (heads, head size, T, q tile rows); the kv tile is the sequence
    "12x64_paired_groups_256_rows": (12, 64, 1024, 256),  # the cell's: six groups, three iterations, four parts
    "12x64_512_rows": (12, 64, 1024, 512), "12x64_1024_rows": (12, 64, 1024, 1024),
    "2x64_no_loop": (2, 64, 1024, 256), "6x64_odd_group_count": (6, 64, 1024, 256),
    "4x128_one_head_a_group": (4, 128, 1024, 256), "4x64_one_unrolled_pair": (4, 64, 512, 128),
    "1x64_the_whole_width": (1, 64, 256, 128), "3x128_odd_groups_of_one": (3, 128, 512, 256),
}


@pytest.mark.parametrize("case", sorted(_LOOPED))
def test_the_looped_forward_gives_the_references_output_and_lse(case):
    h, d, t, bq = _LOOPED[case]
    q, k, v = _bthd_qkv(h, d, t)
    out, lse, took = _forward(q, k, v, bq, t)
    assert took == {"looped": 1}
    ref_out, ref_lse = _scores_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), rtol=2e-5, atol=2e-5)


_UNROLLED = {  # name: (heads, head size, T, Tk, bq, bk, the refusal's words)
    "25x64_gpt2_xls": (25, 64, 1024, 1024, 256, 1024, "do not group into whole lane tiles"),
    "7x64": (7, 64, 1024, 1024, 256, 1024, "do not group into whole lane tiles"),
    "t2048_two_kv_steps": (12, 64, 2048, 2048, 256, 1024, "one step"),
    "tk_longer_than_a_tile": (4, 64, 512, 1024, 256, 512, "one step"),
}


@pytest.mark.parametrize("case", sorted(_UNROLLED))
def test_what_the_loop_refuses_takes_the_unrolled_body_and_gives_the_references_numbers(case):
    h, d, t, tk, bq, bk, why = _UNROLLED[case]
    fa = _fa()
    assert why in fa._fwd_loop_refusal(True, fa._single_step(bq, bk, t, tk), h, d)
    q, k, v = _bthd_qkv(h, d, t, tk)
    out, lse, took = _forward(q, k, v, bq, bk)
    assert took == {"unrolled": 1}
    ref_out, ref_lse = _scores_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("call,want", [
    ((True, True, 12, 64), None), ((True, True, 16, 64), None), ((True, True, 32, 64), None),
    ((True, True, 4, 128), None), ((True, True, 1, 64), None), ((True, True, 2, 256), None),
    ((True, True, 25, 64), "25 heads of 64"), ((True, True, 7, 64), "7 heads of 64"),
    ((True, False, 12, 64), "one step"), ((False, True, 12, 64), "non-causal"),
])
def test_one_predicate_says_which_body_a_forward_takes(call, want):
    refusal = _fa()._fwd_loop_refusal(*call)
    assert (refusal is None) if want is None else (want in refusal), refusal


@pytest.mark.parametrize("h,d,t,bq,bwd", [(4, 64, 256, 128, ("fused", 128)), (6, 64, 256, 128, ("fused", 256)),
                                          (2, 128, 256, 256, ("fused", 128)), (4, 64, 256, 128, (128, 256, 128, 128))])
def test_gradients_through_the_looped_forward_match_xla(h, d, t, bq, bwd):
    q, k, v = _bthd_qkv(h, d, t, b=2, seed=3)
    cot = _bthd_qkv(h, d, t, b=2, seed=4)[0]
    before = _fa().fwd_body_counts()
    got = jax.vjp(lambda *a: flash_attention(*a, causal=True, block_q=bq, block_k=t, layout="BTHD", bwd_blocks=bwd),
                  q, k, v)[1](cot)
    assert _fa().fwd_body_counts()["looped"] == before["looped"] + 1
    want = jax.vjp(lambda *a: _sdpa_xla(*a, is_causal=True, layout="BTHD"), q, k, v)[1](cot)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape,causal,want", [
    ((32, 1024, 12, 64), True, "looped"),  # gpt2s-train-1k's call
    ((2, 1024, 16, 64), True, "looped"), ((2, 1024, 8, 128), True, "looped"),
    ((2, 1024, 25, 64), True, "unrolled"), ((2, 2048, 12, 64), True, "unrolled"),
    ((2, 1024, 12, 64), False, "unrolled"),
])
def test_the_body_counter_says_which_forward_the_dispatchers_call_takes(monkeypatch, shape, causal, want):
    """Through the op, on the table's tiles, traced and not run; the count
    reaches the report and the scrape as flash_tiles_total does."""
    from paddle_tpu import monitor
    from tools import obs_report

    fa = _fa()
    before = fa.fwd_body_counts()
    a = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    jax.make_jaxpr(_attend("BTHD", causal))(a, a, a)
    after = fa.fwd_body_counts()
    assert {b: after[b] - before[b] for b in after} == {"looped": float(want == "looped"),
                                                        "unrolled": float(want == "unrolled")}
    assert obs_report._executor_section(monitor.snapshot())["flash_fwd_calls"] == after
    assert f'flash_fwd_calls_total{{body="{want}"}}' in monitor.to_prometheus()
