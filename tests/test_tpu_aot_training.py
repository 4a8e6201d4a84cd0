"""Compile-only check of the training kernels (flash attention, the fused
lm-head CE, fused Adam) and the train step against a real TPU target
(tests/tpu_aot.py says how; the dispatcher's flash tiles are in
test_tpu_aot_flash_tiles.py and test_tpu_aot_flash_vmem.py)."""
import functools
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.fused_adam import fused_adam
from paddle_tpu.ops.pallas.fused_lmhead_ce import lmhead_ce
from tpu_aot import compiled_text, kernel_names, metric_pattern, mosaic_bodies, own_names, sha
from tpu_aot import tpu_arg, tpu_device, tpu_topology  # noqa: F401  (fixtures)


def test_flash_fwd_bwd_compiles(tpu_arg):
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, block_q=256, block_k=256,
                              layout="BTHD", interpret=False)
        return out.astype(jnp.float32).sum()

    qkv = [tpu_arg((1, 512, 4, 64), jnp.bfloat16)] * 3
    text = compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *qkv)
    assert text.count("tpu_custom_call") >= 3  # fwd, dq, dkv


def test_lmhead_ce_fwd_bwd_compiles(tpu_arg):
    def loss(x, w, labels):
        return lmhead_ce(x, w, labels, interpret=False).sum()

    text = compiled_text(
        jax.grad(loss, argnums=(0, 1)),
        tpu_arg((512, 256), jnp.bfloat16), tpu_arg((2048, 256), jnp.bfloat16),
        tpu_arg((512,), jnp.int32))
    assert text.count("tpu_custom_call") >= 2  # stats; dx and dw from one kernel


def test_fused_adam_compiles(tpu_arg):
    p = tpu_arg((512, 256), jnp.bfloat16)
    m = tpu_arg((512, 256), jnp.float32)
    s = tpu_arg((), jnp.float32)
    text = compiled_text(functools.partial(fused_adam, interpret=False),
                          p, p, m, m, s, s, s)
    assert "tpu_custom_call" in text


def test_oversize_tile_is_refused(tpu_arg):
    """The check is live: at D 16384 the double-buffered (1024, D) x block
    alone is the 64 MB a kernel may use, and the compile-only target
    says so like the chip would. (A large f32 score tile alone does not
    overflow: Mosaic computes it in pieces.)"""
    def loss(x, w, labels):
        return lmhead_ce(x, w, labels, block_n=1024, block_v=256,
                         interpret=False).sum()

    with pytest.raises(Exception, match="vmem"):
        compiled_text(
            loss, tpu_arg((1024, 16384), jnp.bfloat16),
            tpu_arg((256, 16384), jnp.bfloat16), tpu_arg((1024,), jnp.int32))


def test_flash_kernels_carry_their_names_at_gpt2s_widths(tpu_arg):
    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, layout="BTHD", interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    qkv = [tpu_arg((2, 1024, 12, 64), jnp.bfloat16)] * 3
    assert kernel_names(compiled_text(fwd, *qkv)) == ["flash_fwd"]
    names = kernel_names(compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *qkv))
    assert own_names(names) == ["flash_dkv", "flash_dq", "flash_fwd"], names
    rx, fwd_rx = metric_pattern("flash_kernels_roofline"), metric_pattern("fwd_passes_per_step")
    assert all(rx.search(n) for n in names)
    assert sum(bool(fwd_rx.search(n)) for n in names) == 1


# What the forward of gpt2s-train-1k's call handed Mosaic on PR 53's parent
# (19b1a3b; the same lowering, read there): five copies of a body that
# unrolls twelve heads and no loop; its math.exp operations cover 11,010,048
# score elements (12 heads x 256 rows x (256 + 512 + 768 + 1024 + 1024)
# columns), and the compiled kernel is 52,913 bundles of code.
_PARENT_CELL_FWD_EXP_ELEMENTS = 11010048


def _cell_forward(heads=12, head_dim=64, bq=None, batch=32):
    from paddle_tpu.ops import attention

    tiles = attention._flash_tiles(1024, 1024, "BTHD", True, heads=heads, head_dim=head_dim)
    fwd = lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=bq or tiles[0], block_k=tiles[1],  # noqa: E731
                                          layout="BTHD", interpret=False)
    return fwd, (batch, 1024, heads, head_dim)


def test_the_cells_flash_forward_lowers_with_a_loop_over_its_head_groups(tpu_arg):
    """[32, 1024, 768] in bfloat16, the dispatcher's tiles: ONE Mosaic module
    whose every part loops over the head groups, two groups of two heads an
    iteration, so a third of the parent's score elements stand in its text
    (the compiled kernel is 16,887 bundles where the parent's is 52,913; the
    module's OPERATIONS are as many as the parent's, 5,023 | 4,965, since the
    row sums and p @ v are written out a 128-column chunk at a time), and it
    compiles for the described v5e under its name."""
    fwd, shape = _cell_forward()
    (body,) = mosaic_bodies(fwd, *[jax.ShapeDtypeStruct(shape, jnp.bfloat16)] * 3)
    parts = len(sys.modules["paddle_tpu.ops.pallas.flash_attention"]._trims(256, 1024, 0, 1)) + 1
    assert body.count("scf.for") == parts == 5
    exp_elements = sum(int(r) * int(c) for r, c in re.findall(
        r'math\.exp"\(%\d+\)[^\n]*?\(vector<(\d+)x(\d+)xf32>\)', body))
    assert 0 < exp_elements * 3 <= _PARENT_CELL_FWD_EXP_ELEMENTS
    assert kernel_names(compiled_text(fwd, *[tpu_arg(shape, jnp.bfloat16)] * 3)) == ["flash_fwd"]


@pytest.mark.parametrize("heads,head_dim,bq", [(12, 64, 512), (12, 64, 1024), (16, 64, None), (20, 64, None),
                                               (32, 64, None), (8, 128, None)])
def test_the_looped_forward_compiles_at_wider_models_and_longer_q_tiles(tpu_arg, heads, head_dim, bq):
    """Within the VMEM a kernel may use (backend.VMEM_LIMIT): the loop keeps
    no accumulator and no statistics scratch, so 32 heads of 64 at 256 rows
    and twelve at 1,024 rows both fit."""
    fwd, shape = _cell_forward(heads, head_dim, bq, batch=4)
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    before = fa.fwd_body_counts()["looped"]
    assert kernel_names(compiled_text(fwd, *[tpu_arg(shape, jnp.bfloat16)] * 3)) == ["flash_fwd"]
    assert fa.fwd_body_counts()["looped"] == before + 1


def test_lmhead_ce_kernels_carry_their_names_at_gpt2s_widths(tpu_arg):
    """Two kernels since PR 40: the forward sweep, and ONE backward that
    gives dx and dW from one rematerialised tile. It is named
    lmhead_ce_dw because the benchmark's pattern admits stats|dx|dw and
    no PR that claims a gain may edit it."""
    def loss(x, w, labels):
        return lmhead_ce(x, w, labels, interpret=False).sum()

    args = (tpu_arg((2048, 768), jnp.bfloat16), tpu_arg((50304, 768), jnp.bfloat16),
            tpu_arg((2048,), jnp.int32))
    assert kernel_names(compiled_text(loss, *args)) == ["lmhead_ce_stats"]
    names = kernel_names(compiled_text(jax.grad(loss, argnums=(0, 1)), *args))
    assert own_names(names) == ["lmhead_ce_dw", "lmhead_ce_stats"], names
    rx = metric_pattern("lmhead_ce_kernels_roofline")
    assert all(rx.search(n) for n in names)
    assert not rx.search("flash_fwd") and not metric_pattern("flash_kernels_roofline").search(names[0])


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
    text = compiled_text(jax.grad(loss, argnums=(0, 1)), tpu_arg((n, d), jnp.bfloat16),
                          tpu_arg((v, d), jnp.bfloat16), tpu_arg((n,), jnp.int32))
    calls = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert own_names(kernel_names(text)) == ["lmhead_ce_dw", "lmhead_ce_stats"]
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

    text = compiled_text(jax.grad(loss, argnums=(0, 1)), arg((4096, 1600), jnp.bfloat16, P("fsdp", None)),
                          arg((50304, 1600), jnp.bfloat16, P("fsdp", None)), arg((4096,), jnp.int32, P("fsdp")))
    assert kernel_names(text) == ["lmhead_ce_dw", "lmhead_ce_stats"]
    assert "all-gather" in text
    (dw_call,) = [ln for ln in text.splitlines() if "%lmhead_ce_dw" in ln and "tpu_custom_call" in ln]
    assert "bf16[50432,1600]" in dw_call.split(" custom-call(")[0] and "f32[50" not in dw_call


def test_fused_adam_kernel_carries_its_name_at_gpt2s_widths(tpu_arg):
    p = tpu_arg((768, 3072), jnp.bfloat16)
    m = tpu_arg((768, 3072), jnp.float32)
    s = tpu_arg((), jnp.float32)
    text = compiled_text(functools.partial(fused_adam, interpret=False), p, p, m, m, s, s, s)
    assert kernel_names(text) == ["fused_adam"]


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
                        dropout=0.0, dtype="bfloat16")
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
    own = own_names([re.sub(r"\.\d+$", "", n) for n, _ in calls])
    # since PR 43 the flash backward is ONE kernel (dq, dk, dv), named flash_dkv
    assert {k: own.count(k) for k in set(own)} == {
        "flash_fwd": n_layer, "flash_dkv": n_layer,
        "lmhead_ce_stats": 1, "lmhead_ce_dw": 1}
    fwd_rx = metric_pattern("fwd_passes_per_step")
    assert sum(bool(fwd_rx.search(re.sub(r"\.\d+$", "", n))) for n, _ in calls) == n_layer
    scope_of = {"flash_fwd": "fused_attention_tpu/",
                "flash_dkv": "fused_attention_tpu_grad/", "lmhead_ce_stats": "fused_lm_head_ce/",
                "lmhead_ce_dw": "fused_lm_head_ce_grad/"}
    for name, op_name in calls:
        (kernel,) = own_names([name])
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
    ("BHTD", 2048, 2048, 4, 128, True): "ab05d7a00a1f8e0b", ("BTHD", 1024, 1024, 12, 64, False): "8812b777696813f0",
    # PR 53 (parent 19b1a3b): what its looped forward refuses hashes as on ITS parent: causal calls of ONE kv step
    # whose heads do not group into whole lane tiles (GPT-2 XL's 25 heads of 64; 7), the unrolled `single` body
    ("BTHD", 1024, 1024, 25, 64, True): "6f3066afc2743c10", ("BTHD", 1024, 1024, 7, 64, True): "104e870a357cacbe"}


def _kernel_jaxprs(jaxpr):
    return [str(eqn.params["jaxpr"]) + str(eqn.params["grid_mapping"]) for eqn in _pallas_eqns(jaxpr)]


@pytest.mark.parametrize("layout,t,tk,h,d,causal", sorted(_PARENT_FLASH_FULL))
def test_a_non_causal_flash_call_runs_the_parents_kernels(layout, t, tk, h, d, causal):
    from paddle_tpu.ops import attention

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
    assert sha("\n".join(kernels)) == _PARENT_FLASH_FULL[layout, t, tk, h, d, causal]


def test_the_cells_flash_call_keeps_the_parents_backward_beside_its_looped_forward():
    """gpt2s-train-1k's call, two kernels: the fused backward hashes as on PR
    53's parent (19b1a3b: its loop over head groups now shares _over_groups,
    _lanes_of and _own_lanes with the forward and traces as it did); the
    forward no longer does."""
    from paddle_tpu.ops import attention

    bq, bk, bwd = attention._flash_tiles(1024, 1024, "BTHD", True, heads=12, head_dim=64)

    def call(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk, layout="BTHD", bwd_blocks=bwd, interpret=False), q, k, v)
        return vjp(out)

    a = jax.ShapeDtypeStruct((2, 1024, 12, 64), jnp.bfloat16)
    fwd, bwd_kernel = _kernel_jaxprs(jax.make_jaxpr(call)(a, a, a).jaxpr)
    assert sha(bwd_kernel) == "80fc750c59c68ab1"
    assert sha(fwd) != "9f4dc508dd03b549" and ("scan[" in fwd or "while[" in fwd)


# The same programs for a block of another kind: OLMoE's (RMSNorm, RoPE, q/k
# norm, 64 experts of which a token takes 8, untied head) at the widths of
# the cell olmoe-serve-batch, 2 of its 12 layers, no weight allocated.
