"""A forward rule is traced once a step (executor._GradPairing).

A forward op whose generic `<op>_grad` is in the same block is
differentiated where it is traced, and the grad op applies the pullback
made there. Everything else (the forward is not in the trace, an input was
rewritten in between, a hand-wired grad op) differentiates the forward rule
afresh, which is what every grad op did before. Both paths have to give
the same numbers; the counters say which one ran.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, static
from paddle_tpu.framework import (Executor, Program, Scope, program_guard,
                                  registry, unique_name)
from paddle_tpu.framework import executor as executor_mod
from paddle_tpu.framework.backward import append_backward
from paddle_tpu.framework.program import default_main_program
from paddle_tpu.framework.registry import LoweringContext, register_op

TRACES = []  # one entry per trace of the counting rule


@register_op("pairing_counted")
def _counted(ctx, ins, attrs):
    TRACES.append(1)
    x = ins["X"][0]
    return {"Out": x * jnp.tanh(x)}


@register_op("pairing_three_outs")
def _three_outs(ctx, ins, attrs):
    """A float output with a cotangent, one without, and an integer one."""
    x = ins["X"][0]
    return {"A": jnp.sin(x) * 2.0, "B": x * x, "C": jnp.argsort(x, axis=-1).astype(jnp.int32)}


def _own_maker(op, acc, block, grad_needed, no_grad, var_subst=None):
    """A maker of the op's own: wires `<op>_grad` by hand, without the
    `__out__` slots a generic grad op finds its forward by."""
    (x,), (out,) = op._input_vars["X"], op._output_vars["Out"]
    g = block.create_var(name=unique_name.generate(x.name + "@GRAD@OWN"),
                         shape=x.shape, dtype=x.dtype, stop_gradient=True)
    block.append_op("pairing_own_maker_grad",
                    inputs={"X": [x], "Out@GRAD": [acc.finalize(out.name)]},
                    outputs={"X@GRAD": [g]}, attrs=op.all_attrs())
    acc.add_partial(x.name, g)


@register_op("pairing_own_maker", grad_maker=_own_maker)
def _own(ctx, ins, attrs):
    return {"Out": jnp.exp(ins["X"][0] * 0.5)}


@pytest.fixture(autouse=True)
def static_mode():
    paddle.enable_static()
    yield
    paddle.disable_static()


@pytest.fixture
def no_pairing(monkeypatch):
    """Every grad op differentiates its forward rule afresh, as all did
    before: the reference the pairing is held to."""
    plan = executor_mod._GradPairing.__init__

    def switch():
        monkeypatch.setattr(executor_mod._GradPairing, "__init__",
                            lambda self, ops: plan(self, []))

    return switch


def _counter(name):
    return monitor.default_registry().get(name).value


def _counters():
    return np.array([_counter("executor_grad_paired_total"),
                     _counter("executor_grad_retraced_total")])


def _op(op_type, ins, out_slots=("Out",), attrs=None):
    block = default_main_program().current_block()
    outs = {s: [block.create_var(name=unique_name.generate(f"{op_type}_{s}"))] for s in out_slots}
    block.append_op(op_type, inputs=ins, outputs=outs, attrs=attrs or {})
    vs = [outs[s][0] for s in out_slots]
    return vs[0] if len(vs) == 1 else vs


def _mlp(middle="pairing_counted"):
    """fc -> relu -> `middle` -> layer_norm -> fc -> softmax-CE -> mean."""
    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = 11
    with unique_name.guard(), program_guard(main, startup):
        x = static.data("x", shape=[8, 16], dtype="float32")
        y = static.data("y", shape=[8, 1], dtype="int64")
        h = static.nn.fc(x, 32, act="relu")
        h = _op(middle, {"X": [h]})
        h = static.nn.layer_norm(h)
        loss = static.nn.mean(static.nn.softmax_with_cross_entropy(static.nn.fc(h, 10), y))
        grads = [g for _, g in append_backward(loss)]
    return main, startup, loss, grads


def _feed(seed=0):
    r = np.random.RandomState(seed)
    return {"x": r.randn(8, 16).astype("float32"), "y": r.randint(0, 10, (8, 1)).astype("int64")}


def _run(main, startup, fetch, steps=1, feed=None):
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)
    for _ in range(steps):
        out = exe.run(main, feed=feed or _feed(), fetch_list=fetch, scope=scope)
    return out


def _generic_grads(main):
    return sum(registry.generic_grad_forward(op.type) is not None for op in main.global_block().ops)


def test_forward_rule_is_traced_once_and_gradients_are_the_retraced_ones_to_the_bit(no_pairing):
    main, startup, loss, grads = _mlp()
    n = _generic_grads(main)
    assert n >= 7  # mean, softmax-CE, two matmuls and their adds, layer_norm, the counted op, relu
    del TRACES[:]
    before = _counters()
    paired = _run(main, startup, [loss] + grads, steps=3)
    assert len(TRACES) == 1  # three steps, one trace of the step, ONE trace of the rule in it
    assert list(_counters() - before) == [n, 0]

    no_pairing()
    del TRACES[:]
    before = _counters()
    retraced = _run(main, startup, [loss] + grads, steps=3)
    assert len(TRACES) == 2  # the forward op's, and the grad op's own
    assert list(_counters() - before) == [0, n]
    for a, b in zip(paired, retraced):
        np.testing.assert_array_equal(a, b)


def test_eager_interpretation_pairs_too():
    """Op by op on concrete values (what a block with a host op gets): the
    pullback is held between the two ops, the rule runs once a step."""
    main, startup, loss, grads = _mlp()
    ref = _run(main, startup, [loss] + grads)
    scope = Scope()
    Executor().run(startup, scope=scope)
    env = {n: scope.get(n) for n in scope.all_var_names() if hasattr(scope.get(n), "shape")}
    env.update({k: jnp.asarray(v) for k, v in _feed().items()})
    del TRACES[:]
    before = _counters()
    executor_mod.lower_block(LoweringContext(rng_key=jax.random.key(0)), main.global_block(), env)
    assert len(TRACES) == 1
    assert list(_counters() - before) == [_generic_grads(main), 0]
    for v, want in zip([loss] + grads, ref):
        np.testing.assert_allclose(np.asarray(env[v.name]), want, rtol=1e-6, atol=1e-7)


def _rewritten_between():
    """`scale` writes the counted op's INPUT in place, after the forward
    read it and before the grad op reads it."""
    main, startup, loss, grads = _mlp()
    block = main.global_block()
    at = next(i for i, op in enumerate(block.ops) if op.type == "pairing_counted")
    (x,) = block.ops[at]._input_vars["X"]
    block._insert_op(at + 1, "scale", inputs={"X": [x]}, outputs={"Out": [x]},
                     attrs={"scale": 0.5, "bias": 0.0, "bias_after_scale": True})
    return main, startup, loss, grads, 1


def _own_grad_maker():
    main, startup, loss, grads = _mlp(middle="pairing_own_maker")
    assert "pairing_own_maker_grad" in [op.type for op in main.global_block().ops]
    return main, startup, loss, grads, 1


def _forward_not_in_trace():
    """The grad ops of a block lowered alone, over the forward's values: a
    pipeline's backward phase is such a program."""
    main, startup, loss, grads = _mlp()
    return main, startup, loss, grads, _generic_grads(main)


@pytest.mark.parametrize("case", [_forward_not_in_trace, _rewritten_between, _own_grad_maker])
def test_fallbacks_differentiate_afresh_and_are_counted(case, no_pairing):
    main, startup, loss, grads, n_retraced = case()
    n = _generic_grads(main)

    def run():
        if case is not _forward_not_in_trace:
            return _run(main, startup, [loss] + grads)
        scope = Scope()
        Executor().run(startup, scope=scope)
        block = main.global_block()
        first = next(i for i, op in enumerate(block.ops) if op.type == "fill_constant"
                     and "@GRAD" in op.output_arg_names()[0])
        env = {n: scope.get(n) for n in scope.all_var_names() if hasattr(scope.get(n), "shape")}
        env.update({k: jnp.asarray(v) for k, v in _feed().items()})
        ctx = LoweringContext(rng_key=jax.random.key(0))

        @jax.jit
        def two_programs(env):
            env = executor_mod.lower_block(ctx, types.SimpleNamespace(ops=block.ops[:first]), dict(env))
            env = executor_mod.lower_block(ctx, types.SimpleNamespace(ops=block.ops[first:]), env)
            return [env[v.name] for v in [loss] + grads]

        return [np.asarray(v) for v in two_programs(env)]

    before = _counters()
    got = run()
    assert list(_counters() - before) == [n - n_retraced, n_retraced]
    no_pairing()
    for a, b in zip(got, run()):
        np.testing.assert_array_equal(a, b)


def test_every_output_slot_is_written_with_an_integer_output_and_one_without_cotangent(no_pairing):
    """The differentiated forward carries the outputs that take no cotangent
    as aux: integer `C`, and float `B`, for which the hand-wired grad op
    brings no `B@GRAD` (append_backward would bring zeros)."""
    main, startup = Program(), Program()
    with unique_name.guard(), program_guard(main, startup):
        x = static.data("x", shape=[4, 6], dtype="float32")
        block = main.global_block()
        a, b, c = _op("pairing_three_outs", {"X": [x]}, out_slots=("A", "B", "C"))
        seed = static.nn.fill_constant([4, 6], "float32", 1.0)
        gx = block.create_var(name="x@GRAD", shape=x.shape, dtype=x.dtype)
        block.append_op("pairing_three_outs_grad",
                        inputs={"X": [x], "__out__A": [a], "__out__B": [b], "__out__C": [c],
                                "A@GRAD": [seed]},
                        outputs={"X@GRAD": [gx]})
        # an op with an integer output that append_backward wires itself
        w = static.data("w", shape=[4, 6], dtype="float32")
        w.stop_gradient = False
        vals, idx = _op("top_k", {"X": [w]}, out_slots=("Out", "Indices"), attrs={"k": 2})
        (gw,) = [g for g in paddle.static.gradients([static.nn.mean(vals)], [w])]
    feed = {"x": np.random.RandomState(1).randn(4, 6).astype("float32"),
            "w": np.random.RandomState(2).randn(4, 6).astype("float32")}
    before = _counters()
    a_, b_, c_, gx_, vals_, idx_, gw_ = _run(main, startup, [a, b, c, gx, vals, idx, gw], feed=feed)
    assert list(_counters() - before) == [_generic_grads(main), 0]
    xv, wv = feed["x"], feed["w"]
    np.testing.assert_allclose(a_, 2.0 * np.sin(xv), rtol=1e-6)
    np.testing.assert_allclose(b_, xv * xv, rtol=1e-6)
    np.testing.assert_array_equal(c_, np.argsort(xv, axis=-1).astype("int32"))
    assert c_.dtype == np.int32
    np.testing.assert_allclose(gx_, 2.0 * np.cos(xv), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(idx_, np.argsort(-wv, axis=-1)[:, :2])
    np.testing.assert_allclose(vals_, -np.sort(-wv, axis=-1)[:, :2], rtol=1e-6)
    want = np.zeros_like(wv)
    np.put_along_axis(want, idx_.astype("int64"), 1.0 / 8, axis=-1)
    np.testing.assert_allclose(gw_, want, rtol=1e-6)
    no_pairing()
    for got, ref in zip((a_, b_, c_, gx_, vals_, idx_, gw_),
                        _run(main, startup, [a, b, c, gx, vals, idx, gw], feed=feed)):
        np.testing.assert_array_equal(got, ref)


def test_two_grad_ops_of_one_forward_share_its_pullback():
    """`gradients` called twice over one block: both grad ops find the one
    forward, the second is its last consumer."""
    main, startup = Program(), Program()
    with unique_name.guard(), program_guard(main, startup):
        x = static.data("x", shape=[4, 6], dtype="float32")
        x.stop_gradient = False
        y = _op("pairing_counted", {"X": [x]})
        (g1,) = paddle.static.gradients([static.nn.mean(y)], [x])
        (g2,) = paddle.static.gradients([static.nn.mean(static.nn.scale(y, scale=3.0))], [x])
    del TRACES[:]
    before = _counters()
    feed = {"x": np.random.RandomState(3).randn(4, 6).astype("float32")}
    g1_, g2_ = _run(main, startup, [g1, g2], feed=feed)
    assert len(TRACES) == 1
    assert list(_counters() - before) == [_generic_grads(main), 0]
    xv = feed["x"]
    want = (np.tanh(xv) + xv / np.cosh(xv) ** 2) / 24
    np.testing.assert_allclose(g1_, want, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(g2_, 3 * want, rtol=1e-5, atol=1e-7)


def test_counters_reach_the_report_and_the_scrape():
    from tools import obs_report

    _run(*_mlp()[:2], [])
    snap = monitor.snapshot()
    section = obs_report._executor_section(snap)
    assert section["grad_paired"] == _counter("executor_grad_paired_total") > 0
    assert section["grad_retraced"] == _counter("executor_grad_retraced_total")
    prom = monitor.to_prometheus()
    assert "executor_grad_paired_total" in prom and "executor_grad_retraced_total" in prom
